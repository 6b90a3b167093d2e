"""Seconds calibrating a quantized pool against the dense forward
(registry ``setup.calibration_s``, the span ``serving.build.calibrate``
around ``PagedDecoder._calibrate_kv_quant``); 0 where the pool holds
float32 or is not calibrated. A part of ``setup_instance_build_s``. None
where the program keeps no such sum. Layer: Paged decoder."""


def read(run):
    return run["facts"]["jax"].get("calibration_s")
