"""``BENCHMARK.json`` against the rules of its contract that can be
checked without a run, and every file it names."""

import json
import os
import re

import pytest

from benchmark.spec import ROOT, Layout

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
LAYOUT = Layout()
BENCH = LAYOUT.bench


def _line(s, n=200):
    return isinstance(s, str) and 1 <= len(s) <= n and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"]
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    # 2 + 14 x 24 runs of run_seconds + 60, 2 x 90 a cell, 1200 spare
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_configs():
    files = set()
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("benchmark/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k)
            assert not re.search(r"(_dim|_rank)$|embd$|inner|head", k), k
        # the family and its reference are there, and the limits
        fam = LAYOUT.family(cfg["family"])
        LAYOUT.reference(fam.REFERENCE)
        assert set(cfg["limits"]) == {"fit_first_loss_band", "fit_loss_abs",
                                      "fit_grad_rel", "serve_logit_rel"}
        assert cfg["source"] == c["source"]


def test_workloads():
    seen = set()
    four = 0
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert _line(w["why"]) and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        four += w["chips"] == 4
        cell = LAYOUT.cell(w["name"])
        LAYOUT.kind(cell["mix"]["kind"])
        reported = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell["per_layer"]
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    assert 1 <= len(BENCH["workloads"]) <= 24


def test_metrics():
    cells = {w["name"] for w in BENCH["workloads"]}
    names = set()
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    layers = set()
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert _line(m["layer"])
        layers.add(m["layer"])
        LAYOUT.reader(m["name"])  # benchmark/layer_metrics/<name>.py
        for w in m.get("workloads", cells):
            assert m["moves"] in {x["name"] for x in
                                  LAYOUT.cell(w)["end_to_end"]}, (m, w)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    assert 1 <= len(BENCH["per_layer"]) <= 128
    # PERF.md lists the layers under these very names
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, layer


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for d, _, files in os.walk(os.path.join(ROOT, "benchmark")):
        if "__pycache__" in d:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), ROOT)
            assert ok.match(rel) and len(rel) <= 200, rel


@pytest.mark.parametrize("mix", ["serve-chat", "serve-offline"])
def test_serving_mixes_fit_the_model(mix):
    from benchmark import traffic

    m = LAYOUT.mix(mix)
    reqs = traffic.schedule(m)
    assert max(r.prompt_len + r.answer_len for r in reqs) <= m["max_length"]
    assert m["max_length"] <= 1024  # GPT-2's positions
