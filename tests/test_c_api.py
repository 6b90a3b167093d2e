"""Flat C model-building API (native/src/model_capi.cc).

reference: include/flexflow/flexflow_c.h:80-706 — the reference's flat C
surface for non-Python hosts (model_create/create_tensor/dense/compile/
fit/eval/forward). Here the surface embeds CPython and drives
flexflow_tpu.capi_host; this test compiles the C example with gcc,
links libflexflow_tpu_capi.so, and runs it as a REAL C program (own
process, no Python on the host side).
"""

import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(ROOT, "native")
LIB = os.path.join(ROOT, "flexflow_tpu", "native",
                   "libflexflow_tpu_capi.so")
DEMO = os.path.join(ROOT, "examples", "c", "mlp_train.c")

pytestmark = pytest.mark.skipif(shutil.which("gcc") is None
                                or shutil.which("make") is None,
                                reason="no C toolchain")


@pytest.fixture(scope="module")
def c_binary(tmp_path_factory):
    subprocess.run(["make", "-C", NATIVE, "capi"], check=True,
                   capture_output=True)
    out = str(tmp_path_factory.mktemp("capi") / "mlp_train")
    subprocess.run(
        ["gcc", DEMO, f"-I{NATIVE}/include",
         f"-L{os.path.dirname(LIB)}", "-lflexflow_tpu_capi",
         f"-Wl,-rpath,{os.path.dirname(LIB)}", "-o", out],
        check=True, capture_output=True)
    return out


def test_c_host_builds_compiles_trains(c_binary):
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([c_binary], env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-800:])
    assert "ACCURACY" in proc.stdout
    acc = float(proc.stdout.split()[1])
    assert acc > 0.5  # learned well beyond 1/4 chance
    loss = float(proc.stdout.split()[3])
    assert loss > 0.0  # loss metric flowed back through the C surface
