"""The harness end to end at toy sizes on the CPU: each kind of cell
rehearsed through ``run.run_cell``, the result line's keys, the control
refused, no result from the measurement path without a TPU, and a
configuration, a mix, a reader, a family, a reference and a kind added
as new files and new entries alone."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmark.tests import toy

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
SEED = 2 ** 31 + 77  # more than 32 signed bits hold


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    from benchmark.spec import Layout

    return Layout(toy.make_checkout(str(tmp_path_factory.mktemp("checkout"))))


def _run(layout, workload, seconds=1.0):
    import jax

    from benchmark import run

    return run.run_cell(layout, workload, SEED, seconds, False,
                        jax.devices()[:1], time.perf_counter())


@pytest.fixture(scope="module")
def results(layout):
    return {w: _run(layout, w) for w in (
        "gpt2-toy.fit-toy", "gpt2-toy.serve-offline-toy",
        "gpt2-toy.serve-chat-toy")}


@pytest.mark.parametrize("workload,metric", [
    ("gpt2-toy.fit-toy", "train_tokens_per_s"),
    ("gpt2-toy.serve-offline-toy", "serve_tokens_per_s"),
    ("gpt2-toy.serve-chat-toy", "request_p95_ms"),
    ("gpt2-toy.serve-chat-toy", "per_token_p95_ms"),
])
def test_rehearsal_reports_the_cells_metrics(results, workload, metric):
    r = results[workload]
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) >= {metric, "setup_s"}
    assert r["metrics"][metric]["value"] > 0
    assert r["metrics"]["setup_s"]["value"] > 0
    assert r["device"]["platform"] == "cpu"  # and so never a device number


def test_result_line_has_exactly_the_contracts_keys(results):
    for r in results.values():
        line = {k: v for k, v in r.items()
                if k not in ("checks", "facts", "_abandon_threads")}
        assert set(line) == CONTRACT_KEYS
        json.dumps(r)  # every value is JSON
        for m in r["metrics"].values():
            assert set(m) == {"value", "unit"}
        assert set(r["device"]) == {"platform", "kind", "count",
                                    "memory_peak_bytes"}
        for row in r["checks"]:  # each number compared beside its limit
            assert {"name", "value", "limit", "ok"} <= set(row)


def test_chat_scores_requests_due_in_the_window_only(layout, results):
    from benchmark import traffic

    mix = layout.mix("serve-chat-toy")
    reqs = traffic.schedule(mix)
    lo = mix["lead_in_s"]
    due = [r for r in reqs if lo <= r.due_s < lo + 1.0]
    assert results["gpt2-toy.serve-chat-toy"]["attempted"] == len(due)


@pytest.mark.parametrize("workload", ["gpt2-toy.fit-toy",
                                      "gpt2-toy.serve-chat-toy"])
def test_control_is_refused_on_three_seeds(layout, workload):
    """The control proper: the reference in float8 in the program's
    place, against the float32 reference, on three seeds — every one
    above the configuration's limit, and every sound run under it."""
    import jax

    from benchmark import control

    recs = control.readings(layout, workload,
                            [SEED + 1, SEED + 2, SEED + 3],
                            jax.devices()[:1])
    limits = layout.cell(workload)["config"]["limits"]
    key, limit = (("grad_rel", limits["fit_grad_rel"])
                  if "fit" in workload
                  else ("logit_rel", limits["serve_logit_rel"]))
    for rec in recs:
        assert rec["sound"][key] < limit < rec["control"][key], rec
    assert control.separation(recs)[key]["ratio"] >= 3.0


def _cli(args, cwd, timeout=120, env=None):
    return subprocess.run(
        [sys.executable, "benchmark/run.py"] + args, cwd=cwd, timeout=timeout,
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


def test_measurement_path_gives_no_result_on_a_cpu(layout):
    p = _cli(["--workload", "gpt2-toy.fit-toy", "--seed", "1",
              "--seconds", "1", "--trace", "0"], layout.root,
             env={"PYTHONPATH": toy.REPO})
    assert p.returncode == 2, p.stderr[-2000:]
    assert "no result" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_no_result_without_the_program(layout):
    """In a directory that holds only BENCHMARK.json and benchmark/ the
    command fails: the system under test is not there."""
    p = _cli(["--workload", "gpt2-toy.fit-toy", "--seed", "1",
              "--seconds", "1", "--trace", "0"], layout.root)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_four_virtual_devices_rehearse_the_searched_plan(layout):
    """Cell 4's path at toy size: ``compile()`` with the search on over
    a mesh of four (virtual CPU) devices, then the same window."""
    code = (
        "import sys, time, json; t0 = time.perf_counter()\n"
        f"sys.path.insert(0, {toy.REPO!r})\n"
        "import jax\n"
        "from benchmark import run\n"
        "from benchmark.spec import Layout\n"
        f"r = run.run_cell(Layout({layout.root!r}), 'gpt2-toy.fit-toy-x4', "
        f"{SEED}, 0.5, False, jax.devices(), t0)\n"
        "print(json.dumps({k: r[k] for k in ('correct', 'device', "
        "'metrics', 'facts')}))\n")
    p = subprocess.run(
        [sys.executable, "-c", code], timeout=240, capture_output=True,
        text=True, cwd=layout.root,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is True and r["device"]["count"] == 4
    assert r["facts"]["batch"] == 4 and r["facts"]["batch_per_chip"] == 1
    assert r["facts"]["search_profile"]  # the search ran
    assert r["metrics"]["train_tokens_per_s"]["value"] > 0


# ---- adding without editing ------------------------------------------------

DUMMY_KIND = '''
import time


def run(ctx):
    w = ctx.reference.init_weights(ctx.config, ctx.seed)
    ctx.checks.equal("dummy.family", ctx.family.REFERENCE, "dummyref")
    t0 = ctx.window_opens()
    time.sleep(ctx.seconds)
    ctx.window_closed(time.perf_counter())
    ctx.facts["answer"] = float(len(w)) * ctx.mix["factor"]
    return {"attempted": 1, "failed": 0, "end_to_end": {"dummy_rate": 7.0}}
'''


def test_new_cell_from_new_files_and_entries_alone(tmp_path):
    """A configuration, a mix, a kind, a family, a reference, an
    end-to-end metric and a per-layer reader, each a new file or a new
    entry; no file of the benchmark is edited (their bytes are compared
    before and after)."""
    import hashlib

    import jax

    from benchmark import run
    from benchmark.spec import Layout

    root = toy.make_checkout(str(tmp_path))
    base = os.path.join(root, "benchmark")

    def digest():
        out = {}
        for d, _, files in os.walk(base):
            for f in files:
                with open(os.path.join(d, f), "rb") as fh:
                    out[os.path.join(d, f)] = hashlib.sha1(fh.read()).hexdigest()
        return out

    before = digest()
    with open(os.path.join(base, "configs", "dummy.json"), "w") as f:
        json.dump({"name": "dummy", "family": "dummyfam", "n_embd": 8,
                   "n_head": 2, "n_layer": 1, "n_positions": 16,
                   "vocab_size": 32, "activation_function": "gelu",
                   "limits": {}}, f)
    with open(os.path.join(base, "traffic", "noop.json"), "w") as f:
        json.dump({"kind": "noop", "factor": 2.0}, f)
    with open(os.path.join(base, "kinds", "noop.py"), "w") as f:
        f.write(DUMMY_KIND)
    with open(os.path.join(base, "families", "dummyfam.py"), "w") as f:
        f.write("REFERENCE = 'dummyref'\n")
    with open(os.path.join(base, "reference", "dummyref.py"), "w") as f:
        f.write("from benchmark.reference.gpt2 import *  # noqa: F401,F403\n")
    with open(os.path.join(base, "layer_metrics", "dummy_answer.py"), "w") as f:
        f.write("def read(run):\n    return run['facts']['answer']\n")
    with open(os.path.join(base, "layer_metrics", "dummy_nothing.py"), "w") as f:
        f.write("def read(run):\n    return None\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "dummy", "source": "none",
                             "file": "benchmark/configs/dummy.json",
                             "reduced": [], "why": "dummy"})
    bench["workloads"].append({"name": "dummy.noop", "config": "dummy",
                               "traffic": "noop", "chips": 1, "why": "dummy"})
    bench["end_to_end"].append({"name": "dummy_rate", "unit": "1/s",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["dummy.noop"]})
    for name in ("dummy_answer", "dummy_nothing"):
        bench["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "dummy",
            "moves": "dummy_rate", "workloads": ["dummy.noop"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    layout = Layout(root)
    r = run.run_cell(layout, "dummy.noop", 5, 0.05, False,
                     jax.devices()[:1], time.perf_counter())
    assert r["correct"] is True
    assert r["metrics"]["dummy_rate"] == {"value": 7.0, "unit": "1/s"}
    assert set(r["metrics"]) == {"dummy_rate", "setup_s"}
    # the per-layer side: a reader that finds nothing is left out
    cell = layout.cell("dummy.noop")
    names = [m["name"] for m in cell["per_layer"]]
    assert "dummy_answer" in names and "compile_request_s" in names
    got = {n: layout.reader(n).read({"facts": {"answer": 34.0}})
           for n in ("dummy_answer", "dummy_nothing")}
    assert got == {"dummy_answer": 34.0, "dummy_nothing": None}
    after = digest()
    assert {k: v for k, v in after.items() if k in before} == before
    # and the cells that were there are untouched by the additions
    assert [m["name"] for m in
            layout.cell("gpt2-medium.fit-1024")["end_to_end"]] == \
        ["train_tokens_per_s", "setup_s"]
