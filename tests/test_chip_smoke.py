"""chip_smoke.py at toy size on the CPU mesh, and its refusal to pass
without a TPU. The chip itself is reached only through the builder's
tool (``python chip_smoke.py`` from the repo root); what tier-1 can hold
is the control flow, the no-chip exit, and where the compile cache goes."""

import os
import subprocess
import sys

import pytest

import chip_smoke

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_exits_nonzero_and_names_the_platform_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")], env=env,
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stdout
    # it stopped before building a model: no phase line, no result line
    assert "phase=" not in proc.stdout and '"ok"' not in proc.stdout
    assert "nothing was built" in proc.stderr


def test_phases_run_to_completion_at_toy_size(monkeypatch, tmp_path):
    """The same run() main() calls, on the 8-device CPU mesh with the
    kernels interpreted: kernels, serve, then train data-parallel and
    searched, every check in force."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    records = chip_smoke.run(chip_smoke.TOY)
    assert [r["phase"] for r in records] == [
        "kernels", "serve", "train[dp]", "train[searched]"]
    assert records[0]["interpret"] is True
    assert float(records[0]["gated_delta_chunks_o"]) < 1e-4
    assert float(records[0]["paged_chains"]) <= chip_smoke.PAGED_RANGE_TOL
    # the small hybrid's prefills took the whole-sequence kernel
    assert records[1]["hybrid_prefill_path"] == "kernel"
    assert records[1]["hybrid_attention_path"] == "kernel"
    assert records[1]["hybrid_tails_path"] == "kernel"
    # both Mamba-2 families stepped their states by the kernel
    assert float(records[0]["ssd_step_nemotron"]) < 1e-5
    # a delta-rule step's tails: the kernel against slot order
    assert float(records[0]["state_tails_ling"]) <= \
        chip_smoke.STATE_TAILS_RANGE_TOL
    assert records[1]["granite_ssm_step_path"] == "kernel"
    assert records[1]["nemotron_ssm_step_path"] == "kernel"
    assert records[2]["devices"] == 8 and records[2]["attention_path"] == "flash"
    assert records[2]["attention_backward"] == "fused"
    assert records[2]["loss_path"] == records[3]["loss_path"] == "one_pass"
    assert records[3]["compiles_by_epoch"][1] == 0
    assert os.path.isdir(tmp_path / "ledger")  # nothing under the cwd


def test_batch_is_sized_from_device_memory():
    v5e = int(15.75 * 2 ** 30)
    assert chip_smoke.train_batch_that_fits(chip_smoke.GPT2_MEDIUM, v5e) == 4
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.train_batch_that_fits(chip_smoke.GPT2_MEDIUM, 4 * 2 ** 30)


def test_compile_cache_is_placed_from_outside_or_fixed(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the program sets nothing. Unset: one
    fixed directory inside the checkout. Fresh interpreters, because JAX
    reads the variable once at import."""
    code = ("import jax\n"
            "from flexflow_tpu.utils.compile_cache import "
            "configure_compile_cache\n"
            "before = jax.config.jax_compilation_cache_dir\n"
            "print(repr(configure_compile_cache()), repr(before), "
            "repr(jax.config.jax_compilation_cache_dir))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)

    def run(extra):
        out = subprocess.run([sys.executable, "-c", code],
                             env=dict(env, **extra), cwd=tmp_path,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-800:]
        return out.stdout.split()

    outside = str(tmp_path / "placed")
    assert run({"JAX_COMPILATION_CACHE_DIR": outside}) == [
        "None", repr(outside), repr(outside)]
    fixed = os.path.join(_REPO, ".jax_cache")
    assert run({}) == [repr(fixed), "None", repr(fixed)]
