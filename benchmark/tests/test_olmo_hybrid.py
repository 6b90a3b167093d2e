"""The Olmo-Hybrid configuration, its counts, its mix and its readers:
the hand-reckoned numbers of the cut, the file against the catalog, the
mix against the issue's table, the family's round trip, the cell at toy
size through ``run_cell`` on the CPU, every new reader on recorded
readings, and the comparison passing the sound program and refusing the
float8 control at toy size."""

import json
import os
import time

import numpy as np
import pytest

from benchmark import counts_hybrid as counts
from benchmark.spec import Layout
from benchmark.tests import toy

LAYOUT = Layout()
CELL = "olmo-hybrid-pp2.serve-documents"
TOY_CELL = "olmo-hybrid-toy.serve-documents-toy"
SEED = 2 ** 31 + 32
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = ("decode_step_device_ms", "decode_step_roofline",
           "gated_delta_roofline", "prefill_device_ms", "state_bytes_share",
           "kv_blocks_read_share", "slot_occupancy", "loop_step_wall_ms",
           "loop_host_ms", "loop_fetch_ms", "loop_prefill_share",
           "device_idle_share", "idle_no_span_share")


def _config():
    return LAYOUT.cell(CELL)["config"]


def test_counts_match_the_cuts_arithmetic():
    cfg = _config()
    # a linear layer: W_q, W_k 11.06 M each, W_v, W_g, W_o 22.12 M each,
    # W_a, W_b 115,200 each, the MLP 126.81 M
    assert counts.linear_layer_matrix_params(cfg) == (
        2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30
        + 3 * 3840 * 11008) == 215_516_160
    assert counts.full_layer_matrix_params(cfg) == (
        4 * 3840 ** 2 + 3 * 3840 * 11008) == 185_794_560
    # a decode step reads 7.43 GB of matrices (the embedding is looked up)
    assert round(counts.matrix_params(cfg) * 2 / 1e9, 2) == 7.43
    assert round(counts.param_count(cfg) / 1e9, 3) == 4.101
    assert round(counts.param_count(cfg) * 2 / 1e9, 2) == 8.20
    assert counts.state_bytes(cfg) == 30 * 96 * 192 * 4 == 2_211_840
    # a request: 12 x (2.21 MB of state + 69 kB of tail) = 27.4 MB
    assert counts.request_bytes(cfg) == 12 * (2_211_840 + 3 * 11520 * 2)
    assert counts.kv_bytes_per_token(cfg) == 4 * 2 * 3840 * 2 == 61_440
    # 32 slots of 1,200 live tokens: 7.43 + 1.70 + 2.36 GB
    step = counts.decode_bytes_per_step(cfg, 32 * 1200, 32 * 12)
    assert round((step - counts.matrix_params(cfg) * 2) / 1e9, 2) == 4.06
    assert round(32 * 12 * 2 * counts.state_bytes(cfg) / 1e9, 2) == 1.70
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    # the kernel is bound by bytes: 4.4 MB a state against 3.9 MFLOP
    assert counts.gated_delta_least_s(cfg, 384, peaks) == pytest.approx(
        384 * 2 * 2_211_840 / 819e9)


def test_counts_agree_with_the_references_shapes():
    from benchmark.reference import olmo_hybrid

    cfg = _config()
    assert olmo_hybrid.param_count(cfg) == counts.param_count(cfg)
    shapes = olmo_hybrid.param_shapes(cfg)
    assert shapes["l0.wq"] == (3840, 2880) and shapes["l0.wv"] == (3840, 5760)
    assert shapes["l0.conv"] == (4, 11520) and shapes["l0.norm"] == (192,)
    assert shapes["l3.wq"] == (3840, 30, 128)
    assert shapes["l3.q_norm"] == (30, 128) and "l3.conv" not in shapes
    assert shapes["lm_head"] == (3840, 100352)
    assert "l15.wo" in shapes and "l16.wo" not in shapes


def test_configuration_states_the_cut_and_nothing_else():
    cfg = _config()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if '"Olmo-Hybrid-7B"' in line]
    published = rows[0]["config"]
    for k, v in published.items():
        if k in cfg["reduced"]:
            assert cfg[k] != v and cfg["published"][k] == v
        else:
            assert cfg[k] == v, k
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert cfg["source"] == rows[0]["source_url"]
    assert cfg["layer_types"] == published["layer_types"][:16]
    assert cfg["layer_types"].count("full_attention") == 4
    assert "two chips" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {"linear_layers", "projections", "block",
                                   "positions", "dtypes", "weights"}
    assert 0 < cfg["limits"]["serve_logit_rel"] < 1 and cfg["limits_why"]


def test_the_mix_is_the_issues_table_and_fits_the_model():
    from benchmark import traffic

    cell = LAYOUT.cell(CELL)
    mix, cfg = cell["mix"], cell["config"]
    reqs = traffic.schedule(mix)
    assert mix["kind"] == "serve_closed" and mix["trace_seed"] == 32
    assert len(reqs) == 192 and mix["clients"] == mix["decode_slots"] == 32
    assert (mix["max_length"], mix["block_size"]) == (2048, 16)
    assert mix["prefill_buckets"] == [768, 1024, 1536]
    assert mix["kv_dtype"] == "bfloat16" and mix["lead_in_s"] == 20
    assert mix["check"] == {"prompt_len": 1100, "decode_steps": 8}
    assert max(r.prompt_len + r.answer_len for r in reqs) \
        <= mix["max_length"] <= cfg["max_position_embeddings"] == 65536
    assert 512 <= min(r.prompt_len for r in reqs)
    assert max(r.prompt_len for r in reqs) <= 1536
    assert 256 <= min(r.answer_len for r in reqs)
    assert max(r.answer_len for r in reqs) <= 512
    assert traffic.buckets_used(mix, reqs) == [768, 1024, 1536]
    assert cell["workload"]["chips"] == 1
    assert {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert names >= set(READERS)
    # by name, wherever the list holds them: each lists this cell
    entries = {m["name"]: m for m in LAYOUT.bench["per_layer"]}
    for r in READERS:
        assert CELL in entries[r]["workloads"], r


def test_the_family_hands_the_program_the_references_own_arrays():
    import jax

    from benchmark.families import olmo_hybrid as family
    from benchmark.reference import olmo_hybrid as reference
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.ffconst import CompMode

    with open(os.path.join(toy.HERE, "data", "configs",
                           "olmo-hybrid-toy.json")) as f:
        cfg = json.load(f)
    weights = reference.init_weights(cfg, SEED)
    tree = family.to_program(weights, cfg)
    ff = FFModel(FFConfig(batch_size=2, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    family.build(ff, cfg, 2, 16)
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
    # the gates are drawn as the layers' published initialisation does
    a = np.exp(np.asarray(weights["l0.a_log"], np.float32))
    dt = np.log1p(np.exp(np.asarray(weights["l0.dt_bias"], np.float32)))
    assert 0 < a.min() and a.max() <= 16.1
    assert 0.9e-3 <= dt.min() and dt.max() <= 0.11
    with pytest.raises(ValueError, match="no rotary"):
        family.program_config(dict(cfg, rope_parameters={"rope_theta": 1e4}))
    with pytest.raises(ValueError, match="positions exceed"):
        family.build(ff, cfg, 2, 512)


# ---- the toy cell on the CPU -------------------------------------------------

@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    root = toy.make_checkout(str(tmp_path_factory.mktemp("checkout")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "olmo-hybrid-toy", "source": "none: a toy for the CPU tests",
        "file": "benchmark/configs/olmo-hybrid-toy.json", "reduced": [],
        "why": "toy"})
    bench["workloads"].append({
        "name": TOY_CELL, "config": "olmo-hybrid-toy",
        "traffic": "serve-documents-toy", "chips": 1, "why": "toy"})
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
    assert {"serve.paged_logits_vs_reference", "serve.kv_dtype",
            "serve.decode_dispatches_per_step",
            "serve.counters_moved_in_window"} <= names
    assert result["facts"]["serve_check"]["positions"] == 5


def _stats(steps, tokens, blocks, rows, phase):
    return {"decode_steps": steps, "tokens": tokens, "prefill_prompts": 0,
            "knobs": {"decode_slots": 4},
            "kv": {"blocks_read": blocks, "blocks_in_tables": 4 * blocks,
                   "block_size": 16,
                   "state": {"rows": 5, "in_use": 4, "high_water": 4,
                             "row_bytes": 1, "rows_stepped": rows}},
            "loop": {"steps": steps, "phase_s": {
                "wait": 0.0, "admit": 0.0, "prefill": 1.0 * phase,
                "inputs": 0.1 * phase, "dispatch": 0.2 * phase,
                "fetch": 2.0 * phase, "sample": 0.1 * phase,
                "other": 0.1 * phase}}}


def test_every_new_reader_on_recorded_readings(layout):
    """A window of 10 decode steps over 4 slots and 3 state layers (the
    toy's), 30 blocks read, and a reduced trace of those steps at 5 ms
    each with the kernel at 1 ms a step: each reader's number by hand;
    and nothing, without an error, from a program that lacks the
    counters (the parent) or a run that traced nothing."""
    cfg = layout.cell(TOY_CELL)["config"]
    peaks = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    trace = {"programs": {"jit__decode_step": {"count": 10,
                                               "device_s": 0.05},
                          "jit__prefill_step": {"count": 2,
                                                "device_s": 0.03}},
             "ops": [["fusion", 0.02], ["gated_delta_decode", 0.01]],
             "idle_share": 0.25, "window_s": 4.0, "busy_s": 3.0,
             "idle_gaps": [["(no span)", 0.25], ["serving.loop.fetch", 0.5]]}
    run = {"trace": trace, "peaks": peaks, "config": cfg,
           "family": layout.family(cfg["family"]),
           "facts": {"stats0": _stats(0, 0, 0, 0, 0),
                     "stats1": _stats(10, 40, 100, 120, 1)}}

    def read(name):
        return layout.reader(name).read(run)

    state = 4 * 8 * 16 * 4                   # a toy state: H d_k d_v float32
    kv_token = 1 * 2 * 32 * 2                # one full layer, k and v, bf16
    assert counts.state_bytes(cfg) == state
    assert counts.kv_bytes_per_token(cfg) == kv_token
    assert read("decode_step_device_ms") == pytest.approx(5.0)
    assert read("prefill_device_ms") == pytest.approx(15.0)
    # 12 states a step in and out, over 1 GB/s, against 1 ms a step
    assert read("gated_delta_roofline") == pytest.approx(
        100 * (12 * 2 * state / 1e9) / 1e-3)
    # 100 blocks over 40 slot-steps: (100 - 40) * 16 / 10 live tokens
    live = (100 - 40) * 16 / 10
    least = (counts.matrix_params(cfg) * 2 + 12 * 2 * state
             + live * kv_token) / 1e9
    assert read("decode_step_roofline") == pytest.approx(100 * least / 5e-3)
    assert read("state_bytes_share") == pytest.approx(
        100 * 120 * 2 * state / (120 * 2 * state + 100 * 16 * kv_token))
    assert read("kv_blocks_read_share") == pytest.approx(25.0)
    assert read("slot_occupancy") == pytest.approx(100.0)
    assert read("loop_step_wall_ms") == pytest.approx(350.0)
    assert read("loop_host_ms") == pytest.approx(50.0)
    assert read("loop_fetch_ms") == pytest.approx(200.0)
    assert read("loop_prefill_share") == pytest.approx(100 / 3.5)
    assert read("device_idle_share") == pytest.approx(25.0)
    assert read("idle_no_span_share") == pytest.approx(25.0)
    # a program without the state counters: nothing, and no error
    for s in ("stats0", "stats1"):
        del run["facts"][s]["kv"]["state"]
    for name in ("decode_step_roofline", "gated_delta_roofline",
                 "state_bytes_share"):
        assert read(name) is None
    assert read("kv_blocks_read_share") == pytest.approx(25.0)
    # an untraced run: the trace's readers say nothing
    run["trace"] = None
    for name in ("decode_step_device_ms", "prefill_device_ms",
                 "decode_step_roofline", "gated_delta_roofline",
                 "device_idle_share", "idle_no_span_share"):
        assert read(name) is None
    run["facts"] = {"stats0": {}, "stats1": {}}
    for name in READERS:
        assert read(name) is None


def test_the_comparison_passes_the_program_and_refuses_the_control(layout):
    import jax

    from benchmark import control, control_hybrid

    recs = control_hybrid.readings(layout, TOY_CELL,
                                   [SEED + 7919 * i for i in range(3)],
                                   jax.devices()[:1])
    cell = layout.cell(TOY_CELL)
    limit = cell["config"]["limits"]["serve_logit_rel"]
    for rec in recs:
        assert rec["sound"]["logit_rel"] <= limit < \
            rec["control"]["logit_rel"], rec
        assert rec["bfloat16"]["logit_rel"] <= limit
        # the calibration read on each seed's weights, and never fell back
        assert 0 < rec["kv_divergence"] < cell["mix"]["kv_divergence_budget"]
    sep = control.separation(recs)["logit_rel"]
    assert sep["ratio"] >= 3.0
