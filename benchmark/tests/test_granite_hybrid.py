"""The Granite 4.0-H configuration, its counts, its mix and its
comparison: the issue's parameter and pool arithmetic against
``counts_granite_hybrid.py`` and the reference's shapes, the
configuration against the catalog (nothing cut), the mix against the
issue's table, the new entries of ``BENCHMARK.json`` by name, the cell at
toy size through ``run_cell`` on the CPU (both items of its ``check``
list compared), the readers on hand-made counters, the comparison
passing the sound program and refusing the scaled-float8 control at toy
size, and ``run_with`` taking the three calls."""

import json
import os
import time

import pytest

from benchmark import counts_granite_hybrid as counts
from benchmark.spec import Layout
from benchmark.tests import toy

LAYOUT = Layout()
CELL = "granite-4.0-h-micro.serve-rag"
TOY_CELL = "granite-toy.serve-rag-toy"
SEED = 2 ** 31 + 77
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the cell's per-layer quantities, by name: not by where ``per_layer``
# ends. PR 46 shipped eight of the twenty-three its issue named (the
# list held 120 of the 128 it may); PR 49 folded the list and the cell
# joined the other fifteen
SHIPPED = {
    "decode_step_device_ms", "decode_step_roofline", "decode_mamba_device_ms",
    "decode_full_attention_device_ms", "prefill_chunk_device_ms",
    "prefill_chunk_mfu", "chunk_scan_mfu", "state_rows_carried_share"}
JOINED = {
    "decode_matmul_device_ms", "device_idle_share", "idle_no_span_share",
    "device_owned_share", "state_bytes_share", "kv_blocks_read_share",
    "slot_occupancy", "loop_step_wall_ms", "loop_step_wall_max_ms",
    "loop_host_ms", "loop_fetch_ms", "prefill_chunk_window_share",
    "mamba_state_roofline", "chunk_mamba_device_ms",
    "chunk_attention_device_ms"}
ENTRIES = SHIPPED | JOINED


def _config():
    return LAYOUT.cell(CELL)["config"]


def test_counts_match_the_issues_arithmetic():
    cfg = _config()
    # a Mamba mixer: w_in 17.43 M and w_out 8.39 M; with the convolution,
    # the scalars and the gated norm 25.85 M
    assert counts.mamba_matrix_params(cfg) \
        == 2048 * (4096 + 4352 + 64) + 4096 * 2048 == 25_821_184
    small = 4 * 4352 + 4352 + 3 * 64 + 4096
    assert round((counts.mamba_matrix_params(cfg) + small) / 1e6, 2) == 25.85
    assert counts.attention_matrix_params(cfg) \
        == 2 * 2048 * 2048 + 2 * 2048 * 512 == 10_485_760
    assert counts.mlp_params(cfg) == 3 * 2048 * 8192 == 50_331_648
    # a Mamba layer 76.18 M, an attention layer 60.82 M (two gains each)
    assert round((counts.mamba_matrix_params(cfg) + small
                  + counts.mlp_params(cfg) + 2 * 2048) / 1e6, 2) == 76.18
    assert round((counts.attention_matrix_params(cfg)
                  + counts.mlp_params(cfg) + 2 * 2048) / 1e6, 2) == 60.82
    # the embedding, which is the head, once: 3.191 B, 6.38 GB
    assert 100_352 * 2048 == 205_520_896
    assert round(counts.param_count(cfg) / 1e9, 3) == 3.191
    assert round(counts.param_count(cfg) * 2 / 1e9, 2) == 6.38
    # a request: 36 float32 states of 64 x 64 x 128 and 3 x 4,352 tails
    assert counts.state_bytes(cfg) == 64 * 64 * 128 * 4 == 2_097_152
    assert counts.request_bytes(cfg) == 36 * (2_097_152 + 3 * 4352 * 2)
    assert round(counts.request_bytes(cfg) / 1e6, 1) == 76.4
    assert counts.kv_bytes_per_token(cfg) == 4 * 2 * 8 * 64 * 2 == 8192
    # (a model of 40 attention layers of this shape would keep 81,920)
    assert 40 * 2 * 8 * 64 * 2 == 81_920
    # a step at 48 slots: the matrices with the embedding once 6.4 GB, the
    # states in and out 7.2, some 4,100 live tokens a slot 1.6
    step = counts.decode_bytes_per_step(cfg, 48 * 4100, 48 * 36)
    matrices = (counts.layer_matrix_params(cfg) + 205_520_896) * 2
    assert round(matrices / 1e9, 1) == 6.4
    assert round(48 * 36 * 2 * 2_097_152 / 1e9, 1) == 7.2
    assert round((step - matrices - 48 * 36 * 2 * 2_097_152) / 1e9, 1) == 1.6
    assert round(step / 819e9 * 1e3, 1) == 18.6
    # a whole chunk of 2,048 tokens at offset 2,048: 12.2 TFLOP of
    # matrices, 0.3 of scan, 0.2 of scores and sums
    keys = sum(range(2049, 4097))
    assert round(2 * 2048 * counts.layer_matrix_params(cfg) / 1e12, 1) == 12.2
    assert round(counts.scan_flops(cfg, 2048) / 1e12, 1) == 0.3
    attend = counts.chunk_flops(cfg, 2048, keys) \
        - counts.chunk_flops(cfg, 2048, 0)
    assert round(attend / 1e12, 1) == 0.2
    assert round(counts.chunk_flops(cfg, 2048, keys) / 197e12 * 1e3) == 65


def test_counts_agree_with_the_references_shapes():
    from benchmark.reference import granite_hybrid as ref

    cfg = _config()
    assert ref.param_count(cfg) == counts.param_count(cfg)
    assert ref.state_bytes_per_request(cfg) == counts.request_bytes(cfg)
    shapes = ref.param_shapes(cfg)
    assert shapes["embed"] == (100_352, 2048) and "lm_head" not in shapes
    assert shapes["l0.w_in"] == (2048, 8512)
    assert shapes["l0.conv"] == (4, 4352)
    assert shapes["l0.gate_norm"] == (4096,)
    assert shapes["l0.gate"] == shapes["l5.up"] == (2048, 8192)
    assert shapes["l5.wq"] == (2048, 32, 64)
    assert shapes["l5.wk"] == shapes["l5.wv"] == (2048, 8, 64)
    assert shapes["l5.wo"] == (32, 64, 2048)
    assert "l5.w_in" not in shapes and "l0.wq" not in shapes
    assert sum(k.endswith(".w_in") for k in shapes) == 36
    assert sum(k.endswith(".wq") for k in shapes) == 4


def test_configuration_cuts_nothing():
    cfg = _config()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f
                if '"granite-4.0-h-micro"' in line]
    for k, v in rows[0]["config"].items():
        assert cfg[k] == v, k
    assert cfg["reduced"] == [] and "published" not in cfg
    assert cfg["source"] == rows[0]["source_url"]
    assert cfg["num_hidden_layers"] == 40 and cfg["vocab_size"] == 100_352
    assert [i for i, k in enumerate(cfg["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    assert "one chip holds the whole model" in cfg["deployment"]
    assert set(cfg["limits"]) == {"fit_first_loss_band", "fit_loss_abs",
                                  "fit_grad_rel", "serve_logit_rel"}
    assert {"gated_norm", "time_step", "mlp", "positions", "chunk",
            "weights", "cache", "compute_dtype"} <= set(cfg["assumed"])
    entry = LAYOUT.cell(CELL)["config_entry"]
    assert entry["reduced"] == []


def test_the_mix_is_the_issues_table():
    from benchmark import traffic

    cell = LAYOUT.cell(CELL)
    mix, cfg = cell["mix"], cell["config"]
    assert mix["kind"] == "serve_closed_plain_chunked"
    reqs = traffic.schedule(dict(mix, kind="serve_closed"))
    assert len(reqs) == 192 and mix["clients"] == mix["decode_slots"] == 48
    assert (mix["trace_seed"], mix["max_length"], mix["block_size"],
            mix["prefill_chunk"]) == (46, 9216, 64, 2048)
    assert mix["lead_in_s"] == 30
    assert mix["prompt"] == {"dist": "lognormal", "median": 3072,
                             "sigma": 0.7, "min": 1024, "max": 8192}
    assert mix["answer"] == {"dist": "uniform", "min": 512, "max": 1024}
    assert mix["kv_dtype"] == "bfloat16"
    assert mix["check"] == [{"prompt_len": 5000, "decode_steps": 8},
                            {"prompt_len": 1500, "decode_steps": 4}]
    prompts = sorted(r.prompt_len for r in reqs)
    assert max(r.prompt_len + r.answer_len for r in reqs) \
        <= mix["max_length"] <= cfg["max_position_embeddings"]
    assert (prompts[0], prompts[-1]) == (1024, 8192)
    assert round(sum(prompts) / 192) == 3651
    assert 1900 < prompts[48] < 1950                    # a quarter under
    assert round(100 * sum(p > 2048 for p in prompts) / 192) == 72
    assert round(3 * sum(p > 4096 for p in prompts) / 192) == 1
    chunks = [-(-p // 2048) for p in prompts]
    assert round(sum(chunks) / 192, 1) == 2.2
    assert round(100 * (1 - sum(prompts) / (2048 * sum(chunks)))) == 20
    # about 55 % of chunks are not a prompt's first: they carry 36 states
    assert round(100 * (1 - 192 / sum(chunks))) == 55
    # the first check item crosses two chunk boundaries and ends in a
    # partial chunk of 904, the second is one partial chunk from zeros
    assert 5000 - 2 * 2048 == 904 and 1500 < 2048
    # weights, states and keys: 6.38 + 3.7 + 3.6 GB, 80 % of the chip
    states = 49 * counts.request_bytes(cfg)
    blocks = (48 * 144 + 1) * 64 * counts.kv_bytes_per_token(cfg)
    assert round(48 * counts.request_bytes(cfg) / 1e9, 2) == 3.67
    assert round(blocks / 1e9, 2) == 3.62
    total = counts.param_count(cfg) * 2 + states + blocks
    # (the issue's 13.7 is 48 rows; the pool holds the null row besides)
    assert round((total - counts.request_bytes(cfg)) / 1e9, 1) == 13.7
    assert round(total / 1e9, 2) == 13.75
    assert 0.79 < total / 17.18e9 < 0.81


def test_the_new_entries_by_name():
    bench = LAYOUT.bench
    cell = LAYOUT.cell(CELL)
    assert cell["workload"]["chips"] == 1
    assert [m["name"] for m in cell["end_to_end"]] \
        == ["serve_tokens_per_s", "setup_s"]
    mine = {m["name"]: m for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    # the cell reports these and no other quantity of a list
    assert set(mine) == ENTRIES and len(ENTRIES) == 8 + 15
    assert len(bench["per_layer"]) <= 64
    assert {m["name"] for m in cell["per_layer"]} \
        == ENTRIES | {"compile_request_s", "cache_misses_warm"}
    for m in mine.values():
        assert m["moves"] == "serve_tokens_per_s"
        assert LAYOUT.reader(m["name"]).read is not None
    for name in ("decode_step_roofline", "prefill_chunk_mfu",
                 "chunk_scan_mfu", "mamba_state_roofline"):
        assert mine[name]["unit"] == "%"
        assert mine[name]["layer"] == "Kernels"
    serve = next(m for m in bench["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    # (by name, not by place: the next cell is appended behind this one)
    assert CELL in serve["workloads"]
    assert "granite-4.0-h-micro" in [c["name"] for c in bench["configs"]]
    for entry in (cell["workload"], cell["config_entry"]):
        assert len(entry["why"]) <= 200


# ---- the toy cell on the CPU -------------------------------------------------

@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    """The toy checkout of ``toy.py`` with this family's toy cell added
    to it as entries alone (its configuration and mix are data files the
    checkout already copied)."""
    root = toy.make_checkout(str(tmp_path_factory.mktemp("checkout")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "granite-toy", "source": "none: a toy for the CPU tests",
        "file": "benchmark/configs/granite-toy.json", "reduced": [],
        "why": "toy"})
    bench["workloads"].append({
        "name": TOY_CELL, "config": "granite-toy",
        "traffic": "serve-rag-toy", "chips": 1, "why": "toy"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append(TOY_CELL)
    for m in bench["per_layer"]:
        if m["name"] in ENTRIES:
            m["workloads"].append(TOY_CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return Layout(root)


@pytest.fixture(scope="module")
def result(layout):
    import jax

    from benchmark import run

    return run.run_cell(layout, TOY_CELL, SEED, 1.0, False,
                        jax.devices()[:1], time.perf_counter())


def test_toy_cell_runs_and_is_correct(result):
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert result["device"]["platform"] == "cpu"
    names = {row["name"] for row in result["checks"]}
    # every item of the mix's check list was compared
    assert {"serve.paged_logits_vs_reference[39]",
            "serve.paged_logits_vs_reference[11]",
            "serve.decode_dispatches_per_step", "serve.kv_dtype"} <= names


def test_readers_read_the_programs_counters(layout):
    """The per-layer readers that need no trace, on hand-made readings of
    ``stats()`` at a window's two ends; and nothing, without an error,
    from a program that lacks the counters (the parent commit's)."""
    cfg = layout.cell(TOY_CELL)["config"]
    run = {"trace": None, "peaks": None, "config": cfg,
           "family": layout.family(cfg["family"])}

    def stats(k, state=True):
        kv = {"blocks_read": 90 * k, "blocks_in_tables": 300 * k,
              "block_size": 8}
        if state:
            kv["state"] = {"rows_stepped": 90 * k, "rows_started": 12 * k,
                           "rows_carried": 18 * k}
        return {"decode_steps": 10 * k, "tokens": 30 * k,
                "prefill_prompts": 0, "kv": kv,
                "loop": {"prefill_chunks": 10 * k, "prefill_tokens": 120 * k,
                         "prefill_keys": 900 * k,
                         "prefill_keys_window": 0}}

    run["facts"] = {"stats0": stats(0), "stats1": stats(1)}

    def read(name):
        return layout.reader(name).read(run)

    assert read("state_rows_carried_share") == 60.0
    # 90 states in and out beside 90 blocks of 8 tokens' keys and values
    state = 90 * 2 * counts.state_bytes(cfg)
    kv = 90 * 8 * counts.kv_bytes_per_token(cfg)
    assert read("state_bytes_share") == pytest.approx(
        100 * state / (state + kv))
    assert read("kv_blocks_read_share") == 30.0
    from benchmark import plain_chunked

    assert plain_chunked.chunks(run) == {"chunks": 10, "tokens": 120,
                                         "keys": 900}
    # the traced ones read nothing without a trace
    for name in ("decode_step_roofline", "decode_mamba_device_ms",
                 "prefill_chunk_mfu", "chunk_scan_mfu",
                 "decode_full_attention_device_ms", "prefill_chunk_device_ms",
                 "mamba_state_roofline", "chunk_mamba_device_ms",
                 "chunk_attention_device_ms", "decode_matmul_device_ms",
                 "prefill_chunk_window_share", "device_idle_share"):
        assert read(name) is None
    # the parent's counters: a state without the chunks' rows
    run["facts"] = {"stats0": stats(0), "stats1": stats(1)}
    for s in run["facts"].values():
        for key in ("rows_started", "rows_carried"):
            del s["kv"]["state"][key]
    assert read("state_rows_carried_share") is None
    # a program without any counters: nothing, and no error
    run["facts"] = {"stats0": {}, "stats1": {}}
    for name in ENTRIES:
        assert layout.reader(name).read(run) is None, name


def test_the_check_runs_with_no_slot_idle(layout):
    """``program_outputs`` drives the check's items together and with
    every decode slot live, as the window does: each decode step's cached
    lengths are all positive, the items sit in the last slots behind the
    fillers, and each item gets its own count of rows and tokens."""
    import jax
    import numpy as np

    from benchmark import plain_chunked, run, selected

    ctx = run.Ctx(layout, layout.cell(TOY_CELL), SEED, 0.0, False,
                  jax.devices()[:1], time.perf_counter())
    _, inst, _ = selected.build(ctx)
    dec, seen = inst.decoder, []
    decode = dec.decode

    def watched(tokens, tables, lens):
        seen.append((np.array(tables), np.array(lens)))
        return decode(tokens, tables, lens)

    dec.decode = watched
    try:
        in_use = dec.pool.stats()["in_use"]
        outs = plain_chunked.program_outputs(ctx, inst)
        assert dec.pool.stats()["in_use"] == in_use    # all freed
    finally:
        inst.stop()
    items = ctx.mix["check"]
    assert len(seen) == max(i["decode_steps"] for i in items) == 4
    for k, (tables, lens) in enumerate(seen):
        assert (lens > 0).all() and len(lens) == dec.decode_slots == 3
        assert list(lens[-2:]) == [39 + k, 11 + k]
        # every slot has blocks of its own
        firsts = tables[:, 0]
        assert len(set(firsts)) == 3 and (firsts > 0).all()
    for item, (rows, toks) in zip(items, outs):
        assert rows.shape == (1 + item["decode_steps"], 96)
        assert toks.shape == (item["prompt_len"] + item["decode_steps"],)
        assert list(toks[item["prompt_len"]:]) \
            == [int(r.argmax()) for r in rows[:-1]]


def test_the_comparison_passes_the_program_and_refuses_the_control(layout):
    """At toy size over a few seeds and both check items: the sound
    program inside the limit, the scaled-float8 reference in its place
    outside it, and plain float8 further off."""
    import jax

    from benchmark import control_chunked

    recs = control_chunked.readings(
        layout, TOY_CELL, [SEED + 7919 * i for i in range(3)],
        jax.devices()[:1])
    assert len(recs) == 6
    limit = layout.cell(TOY_CELL)["config"]["limits"]["serve_logit_rel"]
    for rec in recs:
        assert rec["sound"] <= limit < rec["control"], rec
        assert rec["bfloat16"] < rec["sound"] and rec["float8"] > limit, rec
    assert control_chunked.separation(recs)["ratio"] > 3.0


def test_run_with_takes_the_three_calls(layout, monkeypatch):
    """``run_with`` builds, warms up and compares through what it is
    given: the chunked kind's build and warm-up wrapped, and a comparison
    of its own, each called once and in that order."""
    import jax

    from benchmark import run, selected

    kind = layout.kind("serve_closed_plain_chunked")
    calls = []

    def build(ctx):
        calls.append("build")
        return selected.build(ctx)

    def warm_up(ctx, inst):
        calls.append("warm_up")
        selected.warm_up(ctx, inst)

    def compare(ctx, inst, weights, checks):
        calls.append("compare")
        assert "embed" in weights
        checks.equal("serve.compared_by_the_callers_own", True, True)

    cell = layout.cell(TOY_CELL)
    ctx = run.Ctx(layout, cell, SEED, 0.3, False, jax.devices()[:1],
                  time.perf_counter())
    out = kind.run_with(ctx, build, warm_up, compare)
    assert calls == ["build", "warm_up", "compare"]
    assert out["failed"] == 0 and out["end_to_end"]["serve_tokens_per_s"] > 0
    assert ctx.checks.correct
    assert "serve.compared_by_the_callers_own" in {
        r["name"] for r in ctx.checks.rows}
    assert ctx.facts["chunks_in_window"] >= 0
