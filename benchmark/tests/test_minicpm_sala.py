"""The MiniCPM-SALA configuration, its counts, its mix and its readers:
the hand-reckoned numbers of the cut, the file against the catalog, the
mix against the issue's table, the family's round trip, the cell at toy
size through ``run_cell`` on the CPU, every new reader on recorded
readings (and nothing from another cell's run), and the comparison
passing the sound program and refusing the float8 control at toy size."""

import json
import os
import time

import numpy as np
import pytest

from benchmark import counts_sala as counts
from benchmark.spec import Layout
from benchmark.tests import toy

LAYOUT = Layout()
CELL = "minicpm-sala-pp2.serve-longdocs"
TOY_CELL = "minicpm-sala-toy.serve-longdocs-toy"
SEED = 2 ** 31 + 34
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = ("decode_step_device_ms", "decode_step_roofline",
           "prefill_chunk_device_ms", "prefill_chunk_mfu",
           "sparse_blocks_read_share", "state_bytes_share",
           "prefill_chunk_window_share", "slot_occupancy",
           "loop_step_wall_ms",
           "loop_step_wall_max_ms", "loop_host_ms", "loop_fetch_ms",
           "device_idle_share", "idle_no_span_share")


def _config():
    return LAYOUT.cell(CELL)["config"]


def test_counts_match_the_cuts_arithmetic():
    cfg = _config()
    # a sparse layer: W_q, W_g, W_o 16.78 M each, W_k, W_v 1.05 M each, the
    # MLP 201.33 M
    assert counts.sparse_layer_matrix_params(cfg) == (
        3 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 16384) == 253_755_392
    assert counts.linear_layer_matrix_params(cfg) == (
        5 * 4096 * 4096 + 3 * 4096 * 16384) == 285_212_672
    # a decode step reads 9.48 GB of matrices (the embedding is looked up)
    assert round(counts.matrix_params(cfg) * 2 / 1e9, 2) == 9.48
    assert round(counts.param_count(cfg) / 1e6) == 5039
    assert round(counts.param_count(cfg) * 2 / 1e9, 2) == 10.08
    assert counts.state_bytes(cfg) == 32 * 128 * 128 * 4 == 2_097_152
    assert counts.request_bytes(cfg) == 12 * 2_097_152      # 25.2 MB
    assert counts.kv_bytes_per_token(cfg) == 4 * 2 * 2 * 128 * 2 == 4096
    assert counts.kernel_bytes_per_token(cfg) == 4 * 32 == 128
    assert counts.block_bytes(cfg) == 64 * 2 * 2 * 128 * 2 == 65_536
    # 16 slots of 24,000 live tokens: 0.81 GB of states, 0.27 GB of
    # selected blocks, 0.05 GB of pooled keys beside the matrices
    step = counts.decode_bytes_per_step(cfg, 16 * 12, 16 * 64, 16 * 24000)
    assert round(16 * 12 * 2 * counts.state_bytes(cfg) / 1e9, 2) == 0.81
    assert round(16 * 64 * 4 * counts.block_bytes(cfg) / 1e9, 2) == 0.27
    assert round((step - counts.matrix_params(cfg) * 2) / 1e9, 2) == 1.12
    # a chunk of 2,048 tokens: 18.2 TFLOP of matrix products and states,
    # 0.5 more of attention once 4,032 keys lie before it
    assert round(counts.chunk_flops(cfg, 2048) / 1e12, 1) == 18.2
    assert round((counts.chunk_flops(cfg, 2048, 30000)
                  - counts.chunk_flops(cfg, 2048)) / 1e12, 2) == 0.54
    # the pool at 16 slots of 33,792 tokens: 2.23 + 0.07 GB and 0.43 of states
    tokens = (16 * 528 + 1) * 64
    assert round(tokens * counts.kv_bytes_per_token(cfg) / 1e9, 2) == 2.21
    assert round(17 * counts.request_bytes(cfg) / 1e9, 2) == 0.43


def test_counts_agree_with_the_references_shapes():
    from benchmark.reference import minicpm_sala

    cfg = _config()
    assert minicpm_sala.param_count(cfg) == counts.param_count(cfg)
    shapes = minicpm_sala.param_shapes(cfg)
    assert shapes["l0.wq"] == (4096, 4096) and shapes["l0.o_norm"] == (128,)
    assert shapes["l1.wk"] == (4096, 256) and shapes["l1.wg"] == (4096, 4096)
    assert "l1.o_norm" not in shapes and shapes["l1.q_norm"] == (128,)
    assert shapes["lm_head"] == (4096, 73448)
    assert "l15.wo" in shapes and "l16.wo" not in shapes
    # the decay of published layers 8 .. 23, not of 0 .. 15
    z = minicpm_sala.sizes(cfg)
    assert (z["first"], z["depth"]) == (8, 32)
    s = minicpm_sala.decay_slopes(32, 8, 32)
    assert s[0] == pytest.approx(2 ** -0.25 * (1 - 8 / 31 + 1e-5))
    assert s[-1] == pytest.approx(2 ** -8 * (1 - 8 / 31 + 1e-5))


def test_configuration_states_the_cut_and_nothing_else():
    cfg = _config()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if '"MiniCPM-SALA"' in line]
    published = rows[0]["config"]
    for k, v in published.items():
        if k in cfg["reduced"]:
            assert cfg[k] != v and cfg["published"][k] == v
        else:
            assert cfg[k] == v, k
    assert cfg["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert cfg["source"] == rows[0]["source_url"]
    assert cfg["mixer_types"] == published["mixer_types"][8:24]
    assert cfg["first_layer"] == 8
    assert "".join("S" if t == "minicpm4" else "L"
                   for t in cfg["mixer_types"]) == "LSLLLLLLSSLLLLSL"
    assert "two chips" in cfg["deployment"]
    assert cfg["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "window_size": 2048, "dense_len": 8192, "init_blocks": 1, "topk": 64}
    assert set(cfg["assumed"]) >= {
        "sparse_config", "decay_slopes", "dense_len_switch", "forced_blocks",
        "mup_denominator", "projections", "dtypes", "weights"}
    assert 0 < cfg["limits"]["serve_logit_rel"] < 1 and cfg["limits_why"]
    assert 0 < cfg["selection_check"]["differing_share"] < 1
    assert 0 < cfg["selection_check"]["score_margin"] < 1


def test_the_mix_is_the_issues_table_and_fits_the_model():
    from benchmark import traffic

    cell = LAYOUT.cell(CELL)
    mix, cfg = cell["mix"], cell["config"]
    reqs = traffic.schedule(dict(mix, kind="serve_closed"))
    assert mix["kind"] == "serve_closed_chunked" and mix["trace_seed"] == 34
    assert len(reqs) == 64 and mix["clients"] == mix["decode_slots"] == 16
    assert (mix["max_length"], mix["block_size"]) == (33792, 64)
    assert mix["prefill_chunk"] == 2048 and "prefill_buckets" not in mix
    assert mix["kv_dtype"] == "bfloat16" and 40 <= mix["lead_in_s"] <= 60
    assert mix["check"] == {"prompt_len": 17000, "decode_steps": 8}
    assert max(r.prompt_len + r.answer_len for r in reqs) \
        <= mix["max_length"] <= cfg["max_position_embeddings"]
    assert 16384 <= min(r.prompt_len for r in reqs)
    assert max(r.prompt_len for r in reqs) <= 32768
    assert 512 <= min(r.answer_len for r in reqs)
    assert max(r.answer_len for r in reqs) <= 1024
    # every prompt is past dense_len: every decode step selects
    assert min(r.prompt_len for r in reqs) > cfg["sparse_config"]["dense_len"]
    assert cell["workload"]["chips"] == 1
    assert {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert names >= set(READERS)
    assert names >= {"compile_request_s", "cache_misses_warm"}
    # this cell's entries by name, wherever the list holds them: an entry
    # is a quantity, and this cell is one of those that report it
    entries = {m["name"]: m for m in LAYOUT.bench["per_layer"]}
    for r in READERS:
        assert CELL in entries[r]["workloads"], r
    assert CELL in [w["name"] for w in LAYOUT.bench["workloads"]]


def test_the_family_hands_the_program_the_references_own_arrays():
    import jax

    from benchmark.families import minicpm_sala as family
    from benchmark.reference import minicpm_sala as reference
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.ffconst import CompMode

    with open(os.path.join(toy.HERE, "data", "configs",
                           "minicpm-sala-toy.json")) as f:
        cfg = json.load(f)
    weights = reference.init_weights(cfg, SEED)
    tree = family.to_program(weights, cfg)
    ff = FFModel(FFConfig(batch_size=2, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    family.build(ff, cfg, 2, 32)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    declared = ff.compiled.params
    assert set(tree) == set(declared)
    for op, ws in declared.items():
        assert set(ws) == set(tree[op]), op
        for name, sds in ws.items():
            got = tree[op][name]
            assert (got.shape, got.dtype) == (sds.shape, sds.dtype), (op, name)
    # the same arrays, not copies: one set of weights on the device
    held = {id(a) for a in weights.values()}
    assert all(id(a) in held for a in jax.tree_util.tree_leaves(tree))
    assert len(jax.tree_util.tree_leaves(tree)) == len(weights)
    assert family.sparse_layer_names(cfg) == ["block1_mixer", "block3_mixer"]
    with pytest.raises(ValueError, match="no rotary"):
        family.program_config(dict(cfg, attn_use_rope=True))
    with pytest.raises(ValueError, match="use_output_gate false"):
        family.program_config(dict(cfg, use_output_gate=False))
    with pytest.raises(ValueError, match="positions exceed"):
        family.build(ff, cfg, 2, 1024)


# ---- the toy cell on the CPU -------------------------------------------------

@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    root = toy.make_checkout(str(tmp_path_factory.mktemp("checkout")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "minicpm-sala-toy", "source": "none: a toy for the CPU tests",
        "file": "benchmark/configs/minicpm-sala-toy.json", "reduced": [],
        "why": "toy"})
    bench["workloads"].append({
        "name": TOY_CELL, "config": "minicpm-sala-toy",
        "traffic": "serve-longdocs-toy", "chips": 1, "why": "toy"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append(TOY_CELL)
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):  # the toy joins what CELL reads
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
    assert {"serve.selection_score_margin", "serve.selection_differing_share",
            "serve.paged_logits_vs_reference", "serve.kv_dtype",
            "serve.decode_dispatches_per_step",
            "serve.counters_moved_in_window"} <= names
    assert result["facts"]["serve_check"]["positions"] == 5
    # a prompt of 100 in chunks of 48 and 4 steps: 40 positions past the
    # toy's dense_len of 64, two sparse layers, two key-value heads
    assert result["facts"]["serve_check"]["triples"] == 40 * 2 * 2
    assert result["facts"]["chunks_in_window"] > 0
    assert result["facts"]["prompt_tokens_in_window"] > 0


def _stats(steps, tokens, read, live, rows, chunks, phase):
    return {"decode_steps": steps, "tokens": tokens, "prefill_prompts": 0,
            "knobs": {"decode_slots": 4},
            "kv": {"block_size": 16,
                   "selected": {"blocks_read": read, "blocks_live": live},
                   "state": {"rows": 5, "in_use": 4, "high_water": 4,
                             "row_bytes": 1, "rows_stepped": rows}},
            "loop": {"steps": steps, "prefill_chunks": chunks,
                     "prefill_tokens": 40 * chunks,
                     "step_wall": {"buckets": {"0.25": 8 * phase,
                                               "0.5": 2 * phase}, "max": 0.4},
                     "phase_s": {
                         "wait": 0.0, "admit": 0.0, "prefill": 1.0 * phase,
                         "inputs": 0.1 * phase, "dispatch": 0.2 * phase,
                         "fetch": 2.0 * phase, "sample": 0.1 * phase,
                         "other": 0.1 * phase}}}


def test_every_new_reader_on_recorded_readings(layout):
    """A window of 10 decode steps over 4 slots and 2 state layers (the
    toy's), 160 of 240 live blocks read, 5 chunks of 40 live tokens, and
    a reduced trace of those steps at 5 ms and those chunks at 20 ms:
    each reader's number by hand; and nothing, without an error, from a
    program that lacks the counters (the parent), a run that traced
    nothing, or another cell's run."""
    cfg = layout.cell(TOY_CELL)["config"]
    peaks = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    trace = {"programs": {"jit__decode_step": {"count": 10,
                                               "device_s": 0.05},
                          "jit__chunk_step": {"count": 4, "device_s": 0.08},
                          "jit__chunk_step_head": {"count": 1,
                                                   "device_s": 0.02}},
             "ops": [["fusion", 0.02]],
             "idle_share": 0.25, "window_s": 0.4, "busy_s": 0.3,
             "idle_gaps": [["(no span)", 0.025], ["serving.loop.fetch", 0.05]]}
    run = {"trace": trace, "peaks": peaks, "config": cfg,
           "family": layout.family(cfg["family"]),
           "facts": {"stats0": _stats(0, 0, 0, 0, 0, 0, 0),
                     "stats1": _stats(10, 40, 160, 240, 80, 5, 1)}}

    def read(name):
        return layout.reader(name).read(run)

    state = 4 * 8 * 8 * 4                    # a toy state: H D D float32
    block = 16 * 2 * 2 * 8 * 2               # k and v, 2 heads of 8, bf16
    assert counts.state_bytes(cfg) == state
    assert counts.block_bytes(cfg) == block
    assert read("decode_step_device_ms") == pytest.approx(5.0)
    assert read("prefill_chunk_device_ms") == pytest.approx(20.0)
    # 240 live blocks over 40 slot-steps: (240 - 40) * 16 / 10 live tokens
    live = (240 - 40) * 16 / 10
    rest = 16 * 2 * block + live * counts.kernel_bytes_per_token(cfg)
    least = (counts.matrix_params(cfg) * 2 + 8 * 2 * state + rest) / 1e9
    assert read("decode_step_roofline") == pytest.approx(100 * least / 5e-3)
    assert read("prefill_chunk_mfu") == pytest.approx(
        100 * counts.chunk_flops(cfg, 40) / 1e12 / 20e-3)
    assert read("sparse_blocks_read_share") == pytest.approx(100 * 160 / 240)
    assert read("state_bytes_share") == pytest.approx(
        100 * 8 * 2 * state / (8 * 2 * state + rest))
    assert read("prefill_chunk_window_share") == pytest.approx(
        100 * 0.1 / 0.4)
    assert read("slot_occupancy") == pytest.approx(100.0)
    assert read("loop_step_wall_ms") == pytest.approx(350.0)
    assert read("loop_step_wall_max_ms") == pytest.approx(500.0)
    assert read("loop_host_ms") == pytest.approx(50.0)
    assert read("loop_fetch_ms") == pytest.approx(200.0)
    assert read("device_idle_share") == pytest.approx(25.0)
    assert read("idle_no_span_share") == pytest.approx(25.0)
    # a program without the new counters: nothing, and no error
    for s in ("stats0", "stats1"):
        del run["facts"][s]["kv"]["selected"]
        del run["facts"][s]["loop"]["prefill_chunks"]
    for name in ("decode_step_roofline", "prefill_chunk_mfu",
                 "sparse_blocks_read_share", "state_bytes_share"):
        assert read(name) is None
    assert read("slot_occupancy") == pytest.approx(100.0)
    # another family's run (the hybrid cell's configuration, family and
    # programs): a family that counts no chunk, a trace that holds none
    hybrid = LAYOUT.cell("olmo-hybrid-pp2.serve-documents")["config"]
    other = dict(run, config=hybrid, family=layout.family(hybrid["family"]))
    other["trace"] = {**trace, "programs": {
        "jit__decode_step": {"count": 10, "device_s": 0.05},
        "jit__prefill_step": {"count": 2, "device_s": 0.03}}}
    for name in ("prefill_chunk_mfu", "prefill_chunk_device_ms",
                 "sparse_blocks_read_share", "prefill_chunk_window_share"):
        assert layout.reader(name).read(other) is None
    # an untraced run: the trace's readers say nothing
    run["trace"] = None
    for name in ("decode_step_device_ms", "prefill_chunk_device_ms",
                 "decode_step_roofline", "prefill_chunk_mfu",
                 "prefill_chunk_window_share", "device_idle_share",
                 "idle_no_span_share"):
        assert read(name) is None
    run["facts"] = {"stats0": {}, "stats1": {}}
    for name in READERS:
        assert read(name) is None


def test_the_comparison_passes_the_program_and_refuses_the_control(layout):
    import jax

    from benchmark import control, control_selected

    recs = control_selected.readings(layout, TOY_CELL,
                                     [SEED + 7919 * i for i in range(3)],
                                     jax.devices()[:1])
    cell = layout.cell(TOY_CELL)
    cfg = cell["config"]
    limit = cfg["limits"]["serve_logit_rel"]
    for rec in recs:
        assert rec["sound"]["logit_error"] <= limit < \
            rec["control"]["logit_error"], rec
        assert rec["bfloat16"]["logit_error"] <= limit
        assert rec["sound"]["differing_share"] <= \
            cfg["selection_check"]["differing_share"]
        assert rec["sound"]["score_margin"] <= \
            cfg["selection_check"]["score_margin"]
        assert control_selected.refused(rec, cfg)
        assert not control_selected.refused(
            dict(rec, control=rec["sound"]), cfg)
        # the calibration read on each seed's weights, and never fell back
        assert 0 <= rec["kv_divergence"] < cell["mix"]["kv_divergence_budget"]
    assert control.separation(recs)["logit_error"]["ratio"] >= 3.0
