"""Simulator-vs-hardware regression: simulated step time within 2x of
measured for the bench transformers, plus a conv-heavy point so CNN costs
are fit, not extrapolated from transformers.

Fails without a TPU (conftest.py). The default machine model
(detect_machine_model) carries the chip constants of CHIP_PRESETS /
CALIBRATION.md; this test asserts those constants track reality within 2x
in BOTH directions. It has not completed on an attached chip; ROADMAP S2
owns the recalibration.
"""

import pytest

# the gate runs EXACTLY the points calibrate() fits — one shared list
from flexflow_tpu.sim.calibrate import CALIBRATION_CONFIGS


@pytest.mark.parametrize("name,build", CALIBRATION_CONFIGS,
                         ids=[n for n, _ in CALIBRATION_CONFIGS])
def test_simulated_step_within_2x_of_measured(name, build):
    from flexflow_tpu.sim import OpCostModel, Simulator, detect_machine_model
    from flexflow_tpu.sim.calibrate import measure_step_time
    ff = build()
    real = measure_step_time(ff, iters=15)
    machine = detect_machine_model(1)
    sim = Simulator(machine, OpCostModel(machine))
    est = sim.simulate_runtime(ff.compiled.ops)
    ratio = est / real
    assert 0.5 <= ratio <= 2.0, (
        f"{name}: simulated {est * 1e3:.2f} ms vs measured "
        f"{real * 1e3:.2f} ms (ratio {ratio:.2f}) — recalibrate via "
        f"flexflow_tpu.sim.calibrate (see CALIBRATION.md)")
