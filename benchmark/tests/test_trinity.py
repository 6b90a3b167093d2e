"""The Trinity-Large configuration, its counts, its mix and its
comparison: the hand-reckoned numbers of the configuration's cut against
``counts_trinity.py`` and the reference's shapes, the mix against the
issue's table, the new entries of ``BENCHMARK.json`` by name, the cell at
toy size through ``run_cell`` on the CPU (both items of its ``check``
list compared), the readers on hand-made counters, and the two-part
comparison passing the sound program and refusing the float8 control at
toy size."""

import json
import os
import time

import pytest

from benchmark import counts_trinity as counts
from benchmark.spec import Layout
from benchmark.tests import toy

LAYOUT = Layout()
CONFIG = "trinity-large-ep8"
CELL = "trinity-large-ep8.serve-mixedlengths"
TOY_CELL = "trinity-toy.serve-mixedlengths-toy"
SEED = 2 ** 31 + 77
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the cell's per-layer quantities (PR 42's twenty-five, under PR 49's
# names), by name: not by where ``per_layer`` ends
ENTRIES = set((
    "decode_step_device_ms", "decode_step_roofline",
    "prefill_chunk_device_ms", "prefill_chunk_mfu",
    "window_attention_roofline", "decode_window_attention_device_ms",
    "decode_full_attention_device_ms", "decode_experts_device_ms",
    "chunk_attention_device_ms", "chunk_experts_device_ms",
    "device_idle_share", "idle_no_span_share", "device_owned_share",
    "window_rows_read_share", "window_rows_reserved_over_used",
    "kv_blocks_read_share", "expert_rows_per_step",
    "expert_load_max_over_mean", "expert_rows_computed_over_named",
    "slot_occupancy", "loop_step_wall_ms", "loop_step_wall_max_ms",
    "loop_host_ms", "loop_fetch_ms", "prefill_chunk_window_share"))


def _config():
    return LAYOUT.cell(CELL)["config"]


def test_counts_match_the_cuts_arithmetic():
    cfg = _config()
    # wq, the gate and wo 3 x 18.9 M, wk and wv 2 x 3.1 M
    assert counts.attention_matrix_params(cfg) \
        == 3 * 3072 * 6144 + 2 * 3072 * 1024 == 62_914_560
    assert counts.expert_params(cfg) == 3 * 3072 * 3072 == 28_311_552
    # router 0.79 M and the shared expert
    assert counts.expert_layer_fixed_params(cfg) == 3072 * 256 + 28_311_552
    # an expert layer 998 M, the dense layer 176 M, the slices 154 M
    expert_layer = (counts.attention_matrix_params(cfg)
                    + counts.expert_layer_fixed_params(cfg)
                    + 32 * counts.expert_params(cfg))
    assert round(expert_layer / 1e6) == 998
    dense_layer = counts.attention_matrix_params(cfg) + 3 * 3072 * 12288
    assert round(dense_layer / 1e6) == 176
    assert 2 * 25_024 * 3072 == 153_747_456
    assert round(counts.param_count(cfg) / 1e9, 2) == 4.32
    assert round(counts.param_count(cfg) * 2 / 1e9, 2) == 8.64
    # a token's keys and values in a layer; a ring; a request at the cap
    assert counts.kv_row_bytes(cfg) == 4096
    assert counts.ring_bytes(cfg) == 16_777_216
    assert counts.request_bytes(cfg, 17_408) == 4096 * (17_408 + 4 * 4096)
    assert counts.request_bytes(cfg, 700) == 4096 * 5 * 700
    # a step at 32 slots of 5,775 tokens: the matrices, the windowed
    # layers' min(length, 4096) rows, the full layer's all
    assert round(counts.matrix_params(cfg) * 2 / 1e9, 2) == 8.49
    assert round(4 * 32 * counts.expert_params(cfg) * 2 / 1e9, 2) == 7.25
    step = counts.decode_bytes_per_step(cfg, 32 * 3293, 32 * 5776)
    kv = step - counts.matrix_params(cfg) * 2
    assert round(kv / 1e9, 2) == 2.48
    assert round(32 * 5776 * 5 * 4096 / 1e9, 2) == 3.79   # every layer full
    assert round(step / 819e9 * 1e3, 1) == 13.4
    # half the held experts idle: that much less is read
    assert step - counts.decode_bytes_per_step(cfg, 32 * 3293, 32 * 5776,
                                               0.5) \
        == 4 * 16 * counts.expert_params(cfg) * 2
    assert counts.window_attend_bytes(cfg, 100) == 4 * 100 * 4096
    # a whole chunk at offset 4,096: 2,048 tokens through 0.54 G fixed
    # parameters, 4,096 held pairs' experts, 11 M and 8 M visible keys a layer
    keys_full = sum(range(4097, 6145))
    keys_win = 2048 * 4096
    flops = counts.chunk_flops(cfg, 2048, 4 * 2048 * 4 // 8, keys_full,
                               keys_win)
    assert round(2 * 2048 * counts.layer_fixed_params(cfg) / 1e12, 2) == 2.23
    assert round(flops / 1e12, 2) == 3.54


def test_counts_agree_with_the_references_shapes():
    from benchmark.reference import trinity

    cfg = _config()
    assert trinity.param_count(cfg) == counts.param_count(cfg)
    shapes = trinity.param_shapes(cfg)
    assert shapes["l0.mlp.up"] == (3072, 12_288)
    assert shapes["l1.router"] == (3072, 256)
    assert shapes["l1.bias"] == (256,)
    assert shapes["l1.experts.gate"] == (32, 3072, 3072)
    assert shapes["l1.shared.down"] == (3072, 3072)
    assert shapes["l2.wq"] == shapes["l2.wg"] == (3072, 48, 128)
    assert shapes["l2.wk"] == (3072, 8, 128)
    assert shapes["l2.wo"] == (48, 128, 3072)
    assert shapes["l2.q_norm"] == shapes["l2.k_norm"] == (128,)
    assert shapes["lm_head"] == (3072, 25_024)
    assert "l0.router" not in shapes and "l1.mlp.up" not in shapes


def test_configuration_states_the_cut_and_nothing_else():
    cfg = _config()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f
                if '"Trinity-Large-Preview"' in line]
    published = rows[0]["config"]
    for k, v in published.items():
        if k in cfg["reduced"]:
            assert cfg[k] != v and cfg["published"][k] == v
        else:
            assert cfg[k] == v, k
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_dense_layers", "num_experts",
                              "vocab_size"]
    assert cfg["source"] == rows[0]["source_url"]
    # the layers kept are the published 5 to 9: the last dense one and a
    # whole period behind it, three windowed layers to a full one
    first = cfg["first_layer"]
    types = published["layer_types"]
    assert types[first:first + 5] == cfg["layer_types"]
    assert first + 1 == published["num_dense_layers"]
    assert (types.count("sliding_attention"),
            types.count("full_attention")) == (45, 15)
    assert cfg["layer_types"][1:].count("sliding_attention") == 3
    # the floors: a whole period, four layers behind the dense ones,
    # >= 8 experts, >= an eighth of the rows
    assert cfg["num_hidden_layers"] - cfg["num_dense_layers"] == 4
    assert cfg["num_experts"] == 32 >= 8
    assert cfg["vocab_size"] * 8 >= published["vocab_size"]
    assert "8 chips" in cfg["deployment"]
    assert set(cfg["routing_check"]) == {"score_margin", "differing_share"}
    # the three fit_* are gpt2-medium's and are not read: nothing trains
    # this configuration (``test_spec.py`` wants the four keys of every one)
    assert set(cfg["limits"]) == {"fit_first_loss_band", "fit_loss_abs",
                                  "fit_grad_rel", "serve_logit_rel"}
    assert {"positions", "embedding", "qk_norm", "gate", "norms", "router",
            "weights", "cache"} <= set(cfg["assumed"])
    entry = LAYOUT.cell(CELL)["config_entry"]
    assert entry["reduced"] == cfg["reduced"]


def test_the_mix_is_the_issues_table():
    from benchmark import traffic

    cell = LAYOUT.cell(CELL)
    mix, cfg = cell["mix"], cell["config"]
    assert mix["kind"] == "serve_closed_routed_chunked"
    reqs = traffic.schedule(dict(mix, kind="serve_closed"))
    assert len(reqs) == 256 and mix["clients"] == mix["decode_slots"] == 32
    assert (mix["trace_seed"], mix["max_length"], mix["block_size"],
            mix["lead_in_s"], mix["prefill_chunk"]) == (42, 17_408, 64, 30,
                                                         2048)
    assert mix["prompt"] == {"dist": "lognormal", "median": 4096,
                             "sigma": 0.9, "min": 512, "max": 16_384}
    assert mix["answer"] == {"dist": "uniform", "min": 256, "max": 768}
    assert mix["kv_dtype"] == "bfloat16"
    assert mix["check"] == [{"prompt_len": 6000, "decode_steps": 8},
                            {"prompt_len": 700, "decode_steps": 4}]
    prompts = sorted(r.prompt_len for r in reqs)
    assert max(r.prompt_len + r.answer_len for r in reqs) \
        <= mix["max_length"] <= cfg["max_position_embeddings"]
    assert (prompts[0], prompts[-1]) == (512, 16_384)
    assert round(sum(prompts) / 256) == 5551
    assert sum(p > 4096 for p in prompts) == 128        # half past the window
    assert round(100 * sum(p > 8192 for p in prompts) / 256) == 22
    assert 2200 < prompts[64] < 2300                    # a quarter under
    chunks = [-(-p // 2048) for p in prompts]
    assert round(sum(chunks) / 256, 1) == 3.2
    assert round(100 * (1 - sum(prompts) / (2048 * sum(chunks)))) == 15
    # the first check item wraps the ring inside its third chunk and
    # decodes past it, the second never fills it
    win = cfg["sliding_window"]
    assert 2 * 2048 < win + 1 <= 6000 and 700 + 4 < win
    assert mix["prefill_chunk"] % mix["block_size"] == 0 \
        and win % mix["block_size"] == 0
    # weights and pool: 8.64 GB, 33 rings a windowed layer, the full
    # layer's blocks (every slot's worst case and the null block)
    rings = 33 * 4 * counts.ring_bytes(cfg)
    blocks = (32 * 272 + 1) * 64 * counts.kv_row_bytes(cfg)
    assert round(rings / 1e9, 2) == 2.21 and round(blocks / 1e9, 2) == 2.28
    share = (counts.param_count(cfg) * 2 + rings + blocks) / 17.18e9
    assert 0.75 < share < 0.78
    # were every layer full, 32 worst-case slots would not fit the chip
    assert counts.param_count(cfg) * 2 + 5 * blocks > 17.18e9


def test_the_new_entries_by_name():
    bench = LAYOUT.bench
    cell = LAYOUT.cell(CELL)
    assert cell["workload"]["chips"] == 1
    assert [m["name"] for m in cell["end_to_end"]] \
        == ["serve_tokens_per_s", "setup_s"]
    mine = {m["name"]: m for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    # the cell reports these and no other quantity of a list
    assert set(mine) == ENTRIES and len(ENTRIES) == 25
    assert {m["name"] for m in cell["per_layer"]} >= ENTRIES
    for m in mine.values():
        assert m["moves"] == "serve_tokens_per_s"
        assert LAYOUT.reader(m["name"]).read is not None
    for name in ("decode_step_roofline", "prefill_chunk_mfu",
                 "window_attention_roofline"):
        assert mine[name]["unit"] == "%"
    serve = next(m for m in bench["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    assert CELL in serve["workloads"]
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
        "name": "trinity-toy", "source": "none: a toy for the CPU tests",
        "file": "benchmark/configs/trinity-toy.json", "reduced": [],
        "why": "toy"})
    bench["workloads"].append({
        "name": TOY_CELL, "config": "trinity-toy",
        "traffic": "serve-mixedlengths-toy", "chips": 1, "why": "toy"})
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
    ``stats()`` at a window's two ends; and nothing, without an error,
    from a program that lacks the counters (the parent commit's)."""
    cfg = layout.cell(TOY_CELL)["config"]
    run = {"trace": None, "peaks": None, "config": cfg,
           "family": layout.family(cfg["family"])}

    def moe(steps, idle, rows, computed, held, p_computed, p_held):
        return {"block1_experts": {
            "held": [4, 4], "steps": steps, "idle_held_experts": idle,
            "rows_per_held_expert": rows, "rows_computed": computed,
            "pairs_held": held, "prompt_rows_computed": p_computed,
            "prompt_pairs_held": p_held}}

    def stats(k):
        return {"moe": moe(10 * k, 10 * k, [10 * k, 0, 20 * k, 10 * k],
                           120 * k, 40 * k, 64 * k, 24 * k),
                "decode_steps": 10 * k, "tokens": 30 * k,
                "prefill_prompts": 0,
                "kv": {"blocks_read": 90 * k, "blocks_in_tables": 300 * k,
                       "block_size": 8,
                       "window": {"rows_read": 200 * k, "rows_full": 500 * k,
                                  "rows_reserved": 320 * k, "rows": 32,
                                  "ops": 3, "rows_held": 7}},
                "loop": {"prefill_chunks": 4 * k, "prefill_tokens": 50 * k,
                         "prefill_keys": 900 * k,
                         "prefill_keys_window": 700 * k}}

    run["facts"] = {"stats0": stats(0), "stats1": stats(1)}

    def read(name):
        return layout.reader(name).read(run)

    assert read("window_rows_read_share") == 40.0
    assert read("window_rows_reserved_over_used") == 1.6
    assert read("kv_blocks_read_share") == 30.0
    assert read("expert_rows_per_step") == 1.0
    assert read("expert_load_max_over_mean") == 2.0
    assert read("expert_rows_computed_over_named") == 184 / 64
    from benchmark import routed_chunked

    assert routed_chunked.chunks(run) == {
        "chunks": 4, "tokens": 50, "keys_full": 900, "keys_window": 700,
        "pairs_held": 24}
    assert routed_chunked.window_rows(run)["steps"] == 10
    # the traced ones read nothing without a trace
    for name in ("decode_step_roofline", "prefill_chunk_mfu",
                 "window_attention_roofline", "chunk_attention_device_ms",
                 "decode_window_attention_device_ms"):
        assert read(name) is None
    # a program without the counters: nothing, and no error
    run["facts"] = {"stats0": {}, "stats1": {}}
    for name in ENTRIES:
        if name.startswith(("loop_", "slot_")):
            continue
        assert layout.reader(name).read(run) is None, name


def test_the_comparison_passes_the_program_and_refuses_the_control(layout):
    """Both parts at toy size over a few seeds and both check items: the
    sound program inside every limit, the float8 reference in its place
    outside one at least."""
    import jax

    from benchmark import control_routed_chunked

    recs = control_routed_chunked.readings(
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
