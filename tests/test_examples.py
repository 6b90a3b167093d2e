"""Example scripts stay runnable (reference: tests/multi_gpu_tests.sh runs
the example programs; here a fast subset runs on the hermetic CPU mesh)."""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(rel_dir, script, args=()):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # 4 emulated devices, not 8: on a 1-core host XLA's CPU collective
    # rendezvous (20s arrival timeout) can spuriously trip with 8 device
    # threads timesharing one core on larger models; 8-way sharding
    # correctness is covered by the in-suite mesh tests
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    # hermetic: ONLY the repo on PYTHONPATH
    env["PYTHONPATH"] = _REPO
    cwd = os.path.join(_REPO, rel_dir)
    proc = subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    return proc.stdout


def test_native_mnist_mlp_example():
    out = _run("examples/python/native", "mnist_mlp.py",
               ["--epochs", "2", "--batch-size", "64"])
    assert "THROUGHPUT" in out


def test_native_nmt_example():
    out = _run("examples/python/native", "nmt.py",
               ["--epochs", "1", "--batch-size", "32"])
    assert "THROUGHPUT" in out


def test_native_dlrm_example():
    out = _run("examples/python/native", "dlrm.py",
               ["--epochs", "1", "--batch-size", "32"])
    assert "THROUGHPUT" in out


def test_keras_mnist_example_gate():
    out = _run("examples/python/keras", "mnist_mlp.py")
    assert "PASS" in out


def test_pytorch_cnn_import_example():
    out = _run("examples/python/pytorch", "cnn_import.py")
    assert "max|diff|" in out
