"""Simulator calibration against real hardware.

The reference times real kernels per (op, view) inside the search
(reference: Op::inner_measure_operator_cost, src/runtime/model.cu:17-53 —
cudaEvent warmup+repeat). Per-op microbenchmarking is NOT the fit here:
per-dispatch latency swamps individual kernels, and compiled-mode XLA
fuses across op boundaries anyway (SURVEY.md §7 hard-part 1: "profile
compiled sub-HLOs, not python-level ops"). So calibration fits the
quantity the simulator actually predicts — FULL train-step times:

    real_step ≈ scale * simulated_step + step_overhead

least-squares over three model points (a small transformer exposes the
fixed per-step dispatch overhead; the bench transformer exposes the
efficiency scale; an AlexNet point keeps conv costs fit rather than
extrapolated from transformers). ``scale`` folds into the chip's mxu/hbm
efficiencies, ``step_overhead`` becomes ``TPUChipSpec.step_overhead``.
The fitted v5e constants live in ``CHIP_PRESETS`` (see CALIBRATION.md
for the measured table).

Usage (on a machine with the target chip)::

    from flexflow_tpu.sim.calibrate import calibrate
    result = calibrate()          # builds + times the three configs
    print(result.report())        # markdown table for CALIBRATION.md
    machine = result.machine      # machine model with fitted chip
"""

from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class CalibrationResult:
    chip_name: str
    scale: float            # real/simulated slope (uncalibrated sim)
    step_overhead: float    # fixed per-step seconds (dispatch)
    points: List[Tuple[str, float, float]]  # (config, real_s, sim_s)
    machine: object         # MachineModel with the fitted chip

    def report(self) -> str:
        lines = [
            "| config | measured step | simulated (calibrated) | ratio |",
            "|---|---|---|---|",
        ]
        for name, real, sim in self.points:
            lines.append(
                f"| {name} | {real * 1e3:.2f} ms | {sim * 1e3:.2f} ms "
                f"| {sim / real:.2f} |"
            )
        lines.append("")
        lines.append(
            f"fit: scale={self.scale:.3f}, "
            f"step_overhead={self.step_overhead * 1e3:.2f} ms "
            f"(chip {self.chip_name})"
        )
        return "\n".join(lines)


def measure_step_time(ff, batch: Optional[int] = None,
                      seq: Optional[int] = None,
                      hidden: Optional[int] = None,
                      warmup: int = 3, iters: int = 20) -> float:
    """Execution-fenced train-step timing (the bench.py protocol: the loss
    of iteration N depends on iteration N-1's params, so ONE value fetch at
    the end fences the whole chain). Input/label arrays are synthesized from the
    compiled model's tensor specs, so any workload (transformer, CNN, …)
    times the same way; the legacy (batch, seq, hidden) positionals are
    accepted and ignored."""
    import jax

    from ..runtime.profiling import _min_vocab_bound, synth_array

    cm = ff.compiled
    rng = np.random.default_rng(0)
    # ids span the smallest embedding table so gathers touch a realistic
    # row spread, not two cache-hot rows
    bound = _min_vocab_bound(ff)
    xs = [jax.device_put(synth_array(t, rng, int_high=bound), sh)
          for t, sh in zip(cm.input_tensors, cm.input_shardings)]
    # the compiler records the label's true spec (shape (batch, 1) INT32
    # for sparse CE, logits-shaped float otherwise — compiler.py:306-323);
    # labels stay in {0,1}: always-valid class indices
    yb = jax.device_put(synth_array(cm.label_tensor, rng),
                        cm.label_sharding)
    key = jax.random.key(0)
    p, o = cm.params, cm.opt_state
    for _ in range(warmup):
        p, o, loss, _ = cm.train_step(p, o, key, *xs, yb)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        p, o, loss, _ = cm.train_step(p, o, key, *xs, yb)
    float(loss)
    return (time.perf_counter() - t0) / iters


def _build_transformer(batch, layers, seq, hidden, heads):
    import jax

    from ..config import FFConfig
    from ..core.machine import make_mesh
    from ..ffconst import LossType
    from ..models.transformer import TransformerConfig, build_transformer
    from ..runtime.model import FFModel
    from ..runtime.optimizer import SGDOptimizer

    cfg = TransformerConfig(hidden_size=hidden, num_heads=heads,
                            num_layers=layers, sequence_length=seq)
    ff = FFModel(FFConfig(batch_size=batch, seed=0))
    build_transformer(ff, batch, cfg)
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
               metrics=[],
               mesh=make_mesh({"data": 1}, devices=jax.devices()[:1]))
    return ff


def _build_cnn(batch: int):
    """AlexNet at its native 229x229x3 (the models-zoo builder's default
    — the topology needs the large input; 32x32 collapses at the third
    pool): the conv-heavy calibration point — conv rooflines extrapolated
    from a transformer fit carry a systematic bias this point
    exposes/corrects."""
    import jax

    from ..config import FFConfig
    from ..core.machine import make_mesh
    from ..ffconst import LossType
    from ..models.alexnet import build_alexnet
    from ..runtime.model import FFModel
    from ..runtime.optimizer import SGDOptimizer

    ff = FFModel(FFConfig(batch_size=batch, seed=0))
    build_alexnet(ff, batch)
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
               metrics=[],
               mesh=make_mesh({"data": 1}, devices=jax.devices()[:1]))
    return ff


# (name, builder): one overhead-dominated transformer point, one
# compute-dominated point (the bench transformer, transformer.cc:78-86),
# one conv-heavy CNN point (AlexNet, BASELINE.md's CNN family)
CALIBRATION_CONFIGS = [
    ("small b8 L4 s256 h512", lambda: _build_transformer(8, 4, 256, 512, 8)),
    ("bert-base b8 L12 s512 h1024",
     lambda: _build_transformer(8, 12, 512, 1024, 16)),
    ("alexnet b64 229x229", lambda: _build_cnn(64)),
]


def calibrate(machine=None, configs=None, iters: int = 20) -> CalibrationResult:
    """Fit (scale, step_overhead) on the current device and return a
    machine model with the calibrated chip (least-squares over all
    configured points — two transformers + a CNN by default)."""
    from . import OpCostModel, Simulator, detect_machine_model

    if machine is None:
        machine = detect_machine_model(1)
    configs = configs or CALIBRATION_CONFIGS

    # simulate with a NEUTRAL chip (calibration fields reset) so refitting
    # an already-calibrated preset doesn't double-apply
    from . import SimpleMachineModel

    base_chip = dataclasses.replace(
        machine.chip, mxu_efficiency=0.55, hbm_efficiency=0.8,
        step_overhead=0.0)
    base_machine = SimpleMachineModel(base_chip, machine.num_devices())

    pts = []
    for name, build in configs:
        ff = build()
        real = measure_step_time(ff, iters=iters)
        sim = Simulator(base_machine, OpCostModel(base_machine))
        est = sim.simulate_runtime(ff.compiled.ops)
        pts.append((name, real, est, ff))

    # two-point linear fit real = scale * sim + overhead (least squares if
    # more than two configs are given)
    xs = np.array([p[2] for p in pts])
    ys = np.array([p[1] for p in pts])
    A = np.stack([xs, np.ones_like(xs)], axis=1)
    (scale, overhead), *_ = np.linalg.lstsq(A, ys, rcond=None)
    scale = float(max(scale, 1e-6))
    overhead = float(max(overhead, 0.0))

    chip = dataclasses.replace(
        base_chip,
        mxu_efficiency=base_chip.mxu_efficiency / scale,
        hbm_efficiency=base_chip.hbm_efficiency / scale,
        step_overhead=overhead,
    )
    fitted_machine = SimpleMachineModel(chip, machine.num_devices())
    fsim = Simulator(fitted_machine, OpCostModel(fitted_machine))
    points = [
        (name, real, fsim.simulate_runtime(ff.compiled.ops))
        for name, real, _est, ff in pts
    ]
    return CalibrationResult(chip.name, scale, overhead, points,
                             fitted_machine)


if __name__ == "__main__":
    r = calibrate()
    print(r.report())
