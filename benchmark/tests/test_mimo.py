"""The MiMo-V2.5 configuration, its counts, its mix and its comparison:
the hand-reckoned numbers of the configuration's cut against
``counts_mimo.py`` and the reference's shapes, the configuration against
the catalog's row, the mix against the issue's table, the new entries of
``BENCHMARK.json`` by name, the cell at toy size through ``run_cell`` on
the CPU (both items of its ``check`` list compared), the readers on
hand-made counters, and the two-part comparison passing the sound program
and refusing the float8 control and a reference without its sinks at toy
size."""

import json
import os
import time

import pytest

from benchmark import counts_mimo as counts
from benchmark.spec import Layout
from benchmark.tests import toy

LAYOUT = Layout()
CONFIG = "mimo-v2.5-ep16"
CELL = "mimo-v2.5-ep16.serve-codebases"
TOY_CELL = "mimo-toy.serve-codebases-toy"
SEED = 2 ** 31 + 55
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("full_attention_roofline", "sink_window_attention_roofline",
       "chunk_attention_mfu")
# the cell's per-layer quantities, by name: not by where ``per_layer`` ends
ENTRIES = set((
    "decode_step_device_ms", "decode_step_roofline",
    "prefill_chunk_device_ms", "prefill_chunk_mfu",
    "decode_window_attention_device_ms", "decode_full_attention_device_ms",
    "decode_experts_device_ms", "decode_experts_kernel_share",
    "chunk_attention_device_ms", "chunk_experts_device_ms",
    "device_idle_share", "idle_no_span_share", "device_owned_share",
    "window_rows_read_share", "window_rows_reserved_over_used",
    "kv_blocks_read_share", "expert_rows_per_step",
    "expert_load_max_over_mean", "expert_rows_computed_over_named",
    "slot_occupancy", "loop_step_wall_ms", "loop_step_wall_max_ms",
    "loop_host_ms", "loop_fetch_ms", "loop_prefill_share",
    "prefill_chunk_window_share", "setup_instance_build_s",
    "setup_calibration_s") + NEW)


def _config():
    return LAYOUT.cell(CELL)["config"]


def test_counts_match_the_cuts_arithmetic():
    cfg = _config()
    full, windowed = counts.FULL, counts.WINDOWED
    # wq 50.33 M, wk 3.15 M, wv 2.10 M, wo 33.55 M; windowed 6.29 and 4.19
    assert counts.attention_matrix_params(cfg, full) \
        == 4096 * 64 * 192 + 4096 * 4 * 192 + 4096 * 4 * 128 \
        + 64 * 128 * 4096 == 89_128_960
    assert counts.attention_matrix_params(cfg, windowed) == 94_371_840
    assert counts.expert_params(cfg) == 3 * 4096 * 2048 == 25_165_824
    # 256 of them are 6.44 B a layer: one chip cannot hold one layer whole
    assert round(256 * counts.expert_params(cfg) * 2 / 1e9, 1) == 12.9
    # layer 0 0.581 GB, a windowed expert layer 0.996, the full one 0.986,
    # the two slices 0.312
    dense = counts.attention_matrix_params(cfg, full) + 3 * 4096 * 16384
    assert round(dense * 2 / 1e9, 3) == 0.581
    held = 16 * counts.expert_params(cfg) + 4096 * 256
    assert round((counts.attention_matrix_params(cfg, windowed) + held)
                 * 2 / 1e9, 3) == 0.996
    assert round((counts.attention_matrix_params(cfg, full) + held)
                 * 2 / 1e9, 3) == 0.986
    assert round(2 * 19_072 * 4096 * 2 / 1e9, 3) == 0.312
    assert round(counts.param_count(cfg) * 2 / 1e9, 2) == 6.86
    # a token in the paged pool, a request's rings, the cell's pool
    assert counts.kv_row_bytes(cfg, full) == 4 * 320 * 2 == 2560
    assert counts.kv_row_bytes(cfg, windowed) == 8 * 320 * 2 == 5120
    assert counts.token_bytes(cfg) == 5120
    assert counts.ring_bytes(cfg) == 5 * 128 * 5120 == 3_276_800
    assert round(32 * 33_792 * counts.token_bytes(cfg) / 1e9, 2) == 5.54
    assert counts.request_bytes(cfg, 100) == 100 * (5120 + 5 * 5120)
    assert counts.request_bytes(cfg, 12_288) == 12_288 * 5120 + 3_276_800
    # a step at 32 slots of 12,288 tokens: the matrices, the windowed
    # layers' 128 rows, the full layers' all
    step = counts.decode_bytes_per_step(cfg, 32 * 128, 32 * 12_289)
    kv = step - counts.matrix_params(cfg) * 2
    assert round(counts.full_attend_bytes(cfg, 32 * 12_289) / 1e9, 2) == 2.01
    assert round(counts.window_attend_bytes(cfg, 32 * 128) / 1e6) == 105
    assert round(kv / 1e9, 2) == 2.12
    fixed = (counts.layer_fixed_params(cfg) + 4096 * 19_072) * 2
    assert round(fixed / 1e9, 2) == 1.87
    # half the held experts idle: that much less is read
    assert step - counts.decode_bytes_per_step(cfg, 32 * 128, 32 * 12_289,
                                               0.5) \
        == 6 * 8 * counts.expert_params(cfg) * 2
    # a (query, seen key) pair in one layer
    assert counts.attention_pair_flops(cfg) == 2 * 64 * 320
    # a whole chunk at offset 10,240 that is NOT its prompt's last: the
    # last layer (full) neither projects queries, attends nor routes
    keys_full = sum(range(10_241, 12_289))
    keys_win = 2048 * 128
    not_last = counts.chunk_flops(cfg, 2048, 0, keys_full, keys_win)
    last = counts.chunk_flops(cfg, 2048, 0, keys_full, keys_win, 2048,
                              keys_full)
    skipped = 4096 * 64 * 320 + 4096 * 256
    assert last - not_last == 2.0 * 2048 * skipped \
        + counts.attention_pair_flops(cfg) * keys_full
    assert counts.chunk_attention_flops(cfg, keys_full, keys_win) \
        == 2 * 64 * 320 * (1 * keys_full + 5 * keys_win)
    assert counts.chunk_attention_flops(cfg, keys_full, keys_win, keys_full) \
        == 2 * 64 * 320 * (2 * keys_full + 5 * keys_win)
    assert round(last / 1e12, 2) == 5.46


def test_counts_agree_with_the_references_shapes():
    from benchmark.reference import mimo

    cfg = _config()
    assert mimo.param_count(cfg) == counts.param_count(cfg)
    shapes = mimo.param_shapes(cfg)
    assert shapes["l0.mlp.up"] == (4096, 16_384)
    assert shapes["l1.router"] == (4096, 256) and shapes["l1.bias"] == (256,)
    assert shapes["l1.experts.gate"] == (16, 4096, 2048)
    assert shapes["l0.wq"] == shapes["l1.wq"] == (4096, 64, 192)
    assert shapes["l0.wk"] == (4096, 4, 192) == shapes["l6.wk"]
    assert shapes["l0.wv"] == (4096, 4, 128)
    assert shapes["l1.wk"] == (4096, 8, 192)
    assert shapes["l1.wv"] == (4096, 8, 128)
    assert shapes["l3.wo"] == (64, 128, 4096)
    assert shapes["l1.sinks"] == (64,)
    assert "l0.sinks" not in shapes and "l6.sinks" not in shapes
    assert shapes["lm_head"] == (4096, 19_072)
    assert "l0.router" not in shapes and "l1.mlp.up" not in shapes
    assert sum(1 for k in shapes if k.endswith("norm_in")) == 7
    assert not any("post" in k or "q_norm" in k or "wg" in k for k in shapes)


def test_configuration_states_the_cut_and_nothing_else():
    cfg = _config()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if '"MiMo-V2.5"' in line]
    published = rows[0]["config"]
    for k, v in published.items():
        if k in cfg["reduced"]:
            assert cfg[k] != v and cfg["published"][k] == v
        else:
            assert cfg[k] == v, k
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_layer_pattern",
                              "moe_layer_freq", "n_routed_experts",
                              "vocab_size"]
    assert cfg["source"] == rows[0]["source_url"]
    # every width as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["swa_num_key_value_heads"],
            cfg["head_dim"], cfg["v_head_dim"], cfg["sliding_window"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["intermediate_size"]) == (4096, 64, 4, 8, 192, 128, 128,
                                          2048, 8, 16_384)
    assert cfg["published"]["n_routed_experts"] == 256
    # the layers kept are the published 0 and 6 to 11: the dense layer and
    # one whole period, five windowed layers to a full one, in order
    first = cfg["first_layer"]
    pattern = published["hybrid_layer_pattern"]
    assert [pattern[0]] + pattern[first:first + 6] \
        == cfg["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0]
    assert [published["moe_layer_freq"][0]] \
        + published["moe_layer_freq"][first:first + 6] \
        == cfg["moe_layer_freq"]
    assert (pattern.count(1), pattern.count(0)) == (39, 9)
    # the floors: a whole period behind the dense layer, >= 8 experts, >=
    # an eighth of the rows
    assert cfg["n_routed_experts"] == 16 >= 8
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    for said in ("16 chips", "chip 0", "layers 6 to 11", "6.86 GB"):
        assert said in cfg["deployment"], said
    assert set(cfg["routing_check"]) == {"score_margin", "differing_share"}
    assert set(cfg["limits"]) == {"fit_first_loss_band", "fit_loss_abs",
                                  "fit_grad_rel", "serve_logit_rel"}
    assert {"layer_kinds", "positions", "value_scale", "sink", "heads",
            "attention_chunk_size", "norms", "router", "weights", "cache",
            "left_out"} <= set(cfg["assumed"])
    entry = LAYOUT.cell(CELL)["config_entry"]
    assert entry["reduced"] == cfg["reduced"]


def test_the_family_hands_the_builder_every_published_answer():
    from benchmark.families import mimo as family
    from flexflow_tpu.models.trinity import FULL, SLIDING

    pc = family.program_config(_config())
    assert pc.layer_types == (FULL,) + (SLIDING,) * 5 + (FULL,)
    assert (pc.num_heads, pc.num_kv_heads, pc.num_kv_heads_sliding,
            pc.head_dim, pc.v_head_dim, pc.rotary_dim, pc.window) == (
        64, 4, 8, 192, 128, 64, 128)
    assert (pc.rope_theta, pc.rope_theta_full) == (1e4, 1e7)
    assert pc.sink_layers == (SLIDING,) and pc.value_scale == 0.707
    assert (pc.n_routed, pc.experts_held, pc.experts_per_token,
            pc.n_shared, pc.routed_scale) == (256, (0, 16), 8, 0, 1.0)
    assert not (pc.sandwich_norms or pc.qk_norm or pc.gate
                or pc.scale_embedding)
    assert pc.num_dense == 1 and pc.dense_width == 16_384
    assert family.expert_layer_names(_config()) == [
        f"block{i}_experts" for i in range(1, 7)]
    with pytest.raises(ValueError, match="dense layers"):
        family.program_config(dict(_config(),
                                   moe_layer_freq=[0, 1, 0, 1, 1, 1, 1]))


def test_the_mix_is_the_issues_table():
    from benchmark import traffic

    cell = LAYOUT.cell(CELL)
    mix, cfg = cell["mix"], cell["config"]
    assert mix["kind"] == "serve_closed_routed_chunked"
    reqs = traffic.schedule(dict(mix, kind="serve_closed"))
    assert len(reqs) == 256 and mix["clients"] == mix["decode_slots"] == 32
    assert (mix["trace_seed"], mix["max_length"], mix["block_size"],
            mix["prefill_chunk"]) == (42, 33_792, 64, 2048)
    assert mix["prompt"] == {"dist": "lognormal", "median": 12_288,
                             "sigma": 0.6, "min": 2048, "max": 32_768}
    assert mix["answer"] == {"dist": "uniform", "min": 256, "max": 768}
    assert mix["kv_dtype"] == "bfloat16"
    assert mix["check"] == [{"prompt_len": 6000, "decode_steps": 8},
                            {"prompt_len": 120, "decode_steps": 16}]
    prompts = sorted(r.prompt_len for r in reqs)
    assert max(r.prompt_len + r.answer_len for r in reqs) \
        <= mix["max_length"] <= cfg["max_position_embeddings"]
    assert 2048 <= prompts[0] and prompts[-1] <= 32_768
    assert 11_000 < prompts[128] < 13_500              # the median
    chunks = [-(-p // 2048) for p in prompts]
    assert 6.5 < sum(chunks) / 256 < 8.0
    # the first check item wraps every ring 46 times over; the second
    # fills its rings and first wraps them INSIDE decoding
    win = cfg["sliding_window"]
    assert 6000 // win == 46 and 120 < win <= 120 + 16
    assert mix["prefill_chunk"] % mix["block_size"] == 0 \
        and win == 2 * mix["block_size"]
    # weights and pool: 6.86 GB, 33 rings a windowed layer, the full
    # layers' blocks (every slot's worst case and the null block)
    rings = 33 * counts.ring_bytes(cfg)
    blocks = (32 * 528 + 1) * 64 * counts.token_bytes(cfg)
    assert round(rings / 1e9, 2) == 0.11 and round(blocks / 1e9, 2) == 5.54
    share = (counts.param_count(cfg) * 2 + rings + blocks) / 17.18e9
    assert 0.72 < share < 0.74


def test_the_new_entries_by_name():
    bench = LAYOUT.bench
    cell = LAYOUT.cell(CELL)
    assert cell["workload"]["chips"] == 1
    assert [m["name"] for m in cell["end_to_end"]] \
        == ["serve_tokens_per_s", "setup_s"]
    mine = {m["name"]: m for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    # the cell reports these and no other quantity of a list
    assert set(mine) == ENTRIES and len(ENTRIES) == 31
    assert {m["name"] for m in cell["per_layer"]} >= ENTRIES
    for name, m in mine.items():
        assert m["moves"] == ("setup_s" if name.startswith("setup_")
                              else "serve_tokens_per_s")
        assert LAYOUT.reader(m["name"]).read is not None
    # the three this cell brings: the last of the list, its alone, Kernels
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(NEW)
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
        assert (mine[name]["unit"], mine[name]["layer"],
                mine[name]["source"]) == ("%", "Kernels", "device_trace")
    # Trinity's share counts by Trinity's keys: not this cell's
    assert CELL not in next(m for m in bench["per_layer"]
                            if m["name"] == "window_attention_roofline"
                            )["workloads"]
    serve = next(m for m in bench["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    assert CELL in serve["workloads"] and len(bench["workloads"]) == 10
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
        "name": "mimo-toy", "source": "none: a toy for the CPU tests",
        "file": "benchmark/configs/mimo-toy.json", "reduced": [],
        "why": "toy"})
    bench["workloads"].append({
        "name": TOY_CELL, "config": "mimo-toy",
        "traffic": "serve-codebases-toy", "chips": 1, "why": "toy"})
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
    # every item of the mix's check list was compared, by both parts
    for n in (75, 11):
        assert {f"serve.routing_score_margin[{n}]",
                f"serve.routing_differing_share[{n}]",
                f"serve.paged_logits_vs_reference[{n}]"} <= names
    assert {"serve.decode_dispatches_per_step",
            "serve.attention_path_decode"} <= names
    assert result["facts"]["chunks_in_window"] > 0


def test_readers_read_the_programs_counters(layout):
    """The per-layer readers that need no trace, on hand-made readings of
    ``stats()`` at a window's two ends; the three new shares on a
    hand-made owner table; and nothing, without an error, from a program
    that lacks the counters (the parent commit's)."""
    cfg = layout.cell(TOY_CELL)["config"]
    family = layout.family(cfg["family"])
    run = {"trace": None, "peaks": None, "config": cfg, "family": family}

    def moe(steps, idle, rows, computed, held, p_computed, p_held):
        return {"block1_experts": {
            "held": [4, 4], "steps": steps, "idle_held_experts": idle,
            "rows_per_held_expert": rows, "rows_computed": computed,
            "pairs_held": held, "prompt_rows_computed": p_computed,
            "prompt_pairs_held": p_held}}

    def stats(k, last=True):
        loop = {"prefill_chunks": 4 * k, "prefill_tokens": 50 * k,
                "prefill_keys": 900 * k, "prefill_keys_window": 700 * k}
        if last:
            loop.update(prefill_tokens_last=20 * k, prefill_keys_last=500 * k)
        return {"moe": moe(10 * k, 10 * k, [10 * k, 0, 20 * k, 10 * k],
                           120 * k, 40 * k, 64 * k, 24 * k),
                "decode_steps": 10 * k, "tokens": 30 * k,
                "prefill_prompts": 0,
                "kv": {"blocks_read": 90 * k, "blocks_in_tables": 300 * k,
                       "block_size": 8,
                       "window": {"rows_read": 200 * k, "rows_full": 500 * k,
                                  "rows_reserved": 320 * k, "rows": 16,
                                  "ops": 2, "rows_held": 7}},
                "loop": loop}

    run["facts"] = {"stats0": stats(0), "stats1": stats(1)}

    def read(name):
        return layout.reader(name).read(run)

    assert read("window_rows_read_share") == 40.0
    assert read("window_rows_reserved_over_used") == 1.6
    assert read("kv_blocks_read_share") == 30.0
    assert read("expert_rows_per_step") == 1.0
    assert family.last_chunks(run) == {"tokens": 20, "keys": 500}
    # the traced ones read nothing without a trace
    for name in NEW + ("decode_step_roofline", "prefill_chunk_mfu",
                       "chunk_attention_device_ms"):
        assert read(name) is None
    # what the families' answers are made of: the toy has two full layers
    # at 2 heads (the last of them the model's last) and two windowed at 4
    run["peaks"] = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    pair = 2.0 * 8 * (48 + 32)
    assert family.chunk_attention_least_s(run) == pytest.approx(
        pair * (1 * 900 + 2 * 700 + 500) / 4 / 1e12)
    assert family.decode_step_least_s(run) == pytest.approx(
        counts.decode_bytes_per_step(cfg, 20.0, 50.0, 0.75) / 1e9)
    assert family.full_attend_least_s(run) == pytest.approx(
        2 * 50 * 2 * 80 * 2 / 1e9)
    assert family.window_attend_least_s(run) == pytest.approx(
        2 * 20 * 4 * 80 * 2 / 1e9)
    assert counts.full_attend_bytes(cfg, 50.0) == 2 * 50 * 2 * 80 * 2
    assert counts.window_attend_bytes(cfg, 20.0) == 2 * 20 * 4 * 80 * 2
    # a program that does not count the last chunks: nothing, no error
    run["facts"] = {"stats0": stats(0, last=False),
                    "stats1": stats(1, last=False)}
    assert family.last_chunks(run) is None
    assert family.chunk_attention_least_s(run) is None
    assert family.chunk_least_s(run) is None
    # a program without any of the counters: nothing, and no error
    run["facts"] = {"stats0": {}, "stats1": {}}
    run["peaks"] = None
    for name in ENTRIES:
        if name.startswith(("loop_", "slot_", "setup_")):
            continue
        assert layout.reader(name).read(run) is None, name


def test_the_comparison_passes_the_program_and_refuses_the_controls(layout):
    """Both parts at toy size over a few seeds and both check items: the
    sound program inside every limit, the float8 reference in its place
    outside one at least, and so a reference without its sinks."""
    import jax

    from benchmark import control_mimo

    recs = control_mimo.readings(
        layout, TOY_CELL, [SEED + 7919 * i for i in range(3)],
        jax.devices()[:1])
    assert len(recs) == 6
    cfg = layout.cell(TOY_CELL)["config"]
    limits = {"score_margin": cfg["routing_check"]["score_margin"],
              "differing_share": cfg["routing_check"]["differing_share"],
              "logit_error": cfg["limits"]["serve_logit_rel"]}
    for rec in recs:
        assert all(rec["sound"][k] <= limits[k] for k in limits), rec
        assert any(rec["control"][k] > limits[k] for k in limits), rec
        assert any(rec["no_sink"][k] > limits[k] for k in limits), rec
        assert set(rec) >= {"no_value_scale", "whole_rotary", "bfloat16",
                            "float8_scaled"}
