"""The GLM-5.3-Flash configuration, its counts, its mix and its comparison:
the hand-reckoned numbers of the configuration's cut, the file against the
catalog's row, the mix against the issue's table, the cell's entries by
name, the cell at toy size through ``run_cell`` on the CPU, the new readers
on hand-made counters and a hand-made owner table, and the four-part
comparison passing the sound program and the reference in bfloat16 and
refusing each of the three controls by the part named for it, at toy
size."""

import json
import os
import time

import pytest

from benchmark import counts_glm as counts
from benchmark.spec import Layout
from benchmark.tests import toy

LAYOUT = Layout()
CELL = "glm-5.3-flash-ep8.serve-repositories"
TOY_CELL = "glm-toy.serve-repositories-toy"
SEED = 2 ** 31 + 63
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("decode_select_device_ms", "chunk_kda_device_ms",
       "decode_stream_mix_device_ms", "chunk_stream_mix_device_ms",
       "selected_rows_read_share", "chunk_rows_attended_over_taken",
       "sparse_latent_roofline")
ENTRIES = {
    "slot_occupancy", "decode_step_device_ms", "decode_step_roofline",
    "device_idle_share", "loop_step_wall_ms", "loop_host_ms", "loop_fetch_ms",
    "loop_prefill_share", "loop_step_wall_max_ms", "idle_no_span_share",
    "device_owned_share", "expert_rows_per_step", "expert_load_max_over_mean",
    "expert_rows_computed_over_named", "decode_experts_device_ms",
    "decode_experts_kernel_share", "decode_router_device_ms",
    "chunk_experts_device_ms", "state_bytes_share", "decode_kda_device_ms",
    "kda_state_roofline", "decode_state_write_device_ms",
    "decode_attention_device_ms", "prefill_chunk_device_ms",
    "prefill_chunk_mfu", "prefill_chunk_window_share",
    "chunk_select_device_ms", "chunk_attend_device_ms",
    "setup_instance_build_s", "setup_calibration_s"} | set(NEW)


def _config():
    return LAYOUT.cell(CELL)["config"]


def test_counts_match_the_cuts_arithmetic():
    cfg = _config()
    # a KDA mixer 137.6 M, the sparse latent mixer 124.4 M (its indexer
    # 6.95 M), one expert 25.17 M, one stream mix 0.39 M
    assert counts.kda_matrix_params(cfg) == (
        4 * 4096 * 8192 + 2 * 128 * (4096 + 8192) + 4096 * 64)
    assert round(counts.kda_matrix_params(cfg) / 1e6, 1) == 137.6
    assert counts.indexer_params(cfg) == (
        1536 * 32 * 128 + 4096 * 128 + 4096 * 32)
    assert round(counts.indexer_params(cfg) / 1e6, 2) == 6.95
    assert counts.sparse_matrix_params(cfg) == (
        4096 * 1536 + 1536 * 64 * 256 + 4096 * 512 + 512 * 64 * 512
        + 64 * 256 * 4096 + counts.indexer_params(cfg))
    assert round(counts.sparse_matrix_params(cfg) / 1e6, 1) == 124.4
    assert counts.expert_params(cfg) == 25_165_824
    assert counts.stream_mix_params(cfg) == 16384 * 24 + 3 + 24
    parts = counts.parts(cfg)
    assert parts["kda"] == 4 * counts.kda_matrix_params(cfg)
    assert parts["dense_mlp"] == 3 * 4096 * 12288           # 151.0 M
    assert parts["router_shared"] == 4 * (4096 * 288 + 25_165_824)
    assert parts["experts"] == 4 * 36 * 25_165_824          # 906.0 M a layer
    assert parts["stream_mix"] == 10 * counts.stream_mix_params(cfg)
    assert parts["head"] == 4096 * 19_360
    assert round(counts.param_count(cfg) / 1e9, 2) == 4.72
    # a request: four float32 states, twelve tails and an open pool's sum;
    # a token: one row and a quarter of an index key
    assert counts.state_bytes(cfg) == 64 * 128 * 128 * 4
    assert counts.request_bytes(cfg) == (
        4 * (4_194_304 + 3 * 24_576 * 2) + 128 * 4)
    assert round(counts.request_bytes(cfg) / 1e6, 2) == 17.37
    assert counts.kv_bytes_per_token(cfg) == 1024 + 64
    # the pool at 32 slots of 66,560 tokens beside the weights: 73 %
    mem = counts.memory(cfg, 32, 66_560)
    assert round(mem["weights"] / 1e9, 2) == 9.44
    assert round(mem["rows_and_keys"] / 1e9, 2) == 2.32
    assert round(mem["states"] / 1e9, 2) == 0.57
    assert 0.72 < mem["total"] / 16.9e9 < 0.74
    # a step at 32 slots of 16,384 cached tokens: each reads 2,048 rows
    # and 4,096 pooled keys of its 16,385
    live, read = 32 * 16_385, 32 * counts.rows_taken(cfg, 16_384)
    assert read == 32 * 2048
    step = counts.decode_bytes_by_part(cfg, live, read, 32 * 4, 0.2)
    assert step["states"] == 128 * 2 * 4_194_304            # 1.07 GB
    assert step["index_and_rows"] == live / 4 * 256 + read * 1024
    assert round(step["index_and_rows"] / 1e6, 1) == 100.7
    assert round(step["experts"] / 1e9, 2) == 1.45
    assert sum(step.values()) == counts.decode_bytes_per_step(
        cfg, live, read, 128, 0.2)
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    assert counts.state_step_least_s(cfg, 128, peaks) == pytest.approx(
        128 * 2 * 4_194_304 / 819e9)
    # a chunk of 2,048 tokens behind 10,240: 2.6 TFLOP of matrices, the
    # indexer's scores 0.05, the attention over the rows taken 0.27
    pos = range(10_240, 12_288)
    flops = counts.chunk_flops_by_part(
        cfg, 2048, sum(p // 4 for p in pos), 2048 * 2048,
        2048 * 8 * 4 / 8)
    assert round(flops["matrices"] / 1e12, 2) == 3.83
    assert round(flops["index_scores"] / 1e12, 3) == 0.047
    assert round(flops["attention"] / 1e12, 2) == 0.27
    assert round(flops["experts"] / 1e12, 2) == 0.41
    assert flops["head"] == 0


def test_counts_agree_with_the_references_shapes():
    from benchmark.reference import glm

    cfg = _config()
    assert glm.param_count(cfg) == counts.param_count(cfg)
    shapes = glm.param_shapes(cfg)
    assert shapes["l1.router"] == (4096, 288) and shapes["l1.bias"] == (288,)
    assert shapes["l1.experts.gate"] == (36, 4096, 2048)
    assert shapes["l0.mlp.gate"] == (4096, 12288) and "l0.router" not in shapes
    assert shapes["l0.wf_a"] == (4096, 128)
    assert shapes["l0.wf_b"] == shapes["l0.wg_b"] == (128, 8192)
    assert shapes["l0.dt_bias"] == (8192,) and shapes["l0.a_log"] == (64,)
    assert shapes["l0.conv"] == (4, 3 * 8192)
    assert shapes["l1.wq_b"] == (1536, 64 * 256)     # published layer 3
    assert shapes["l1.wkv_a"] == (4096, 512) and "l1.wf_a" not in shapes
    assert shapes["l1.wkv_b"] == (512, 64 * 512)
    assert shapes["l1.wq_i"] == (1536, 32 * 128)
    assert shapes["l1.wk_i"] == (4096, 128) and shapes["l1.ww_i"] == (4096, 32)
    assert shapes["l3.mix2.w"] == (16384, 24)
    assert shapes["lm_head"] == (4096, 19_360)
    assert glm.layer_kinds(cfg) == [
        (glm.KDA, True), (glm.SPARSE, False), (glm.KDA, False),
        (glm.KDA, False), (glm.KDA, False)]
    assert glm.picks_of(glm.sizes(cfg)) == 511


def test_configuration_states_the_cut_and_nothing_else():
    cfg = _config()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if '"GLM-5.3-Flash"' in line]
    published = rows[0]["config"]
    for k, v in published.items():
        if k in cfg["reduced"]:
            assert cfg[k] != v and cfg["published"][k] == v
        else:
            assert cfg[k] == v, k
    assert cfg["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "indexer_types", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["num_nextn_predict_layers"]) \
        == (5, 36, 19_360, 0)
    assert cfg["layer_types"] == published["layer_types"][2:7]
    assert cfg["mlp_layer_types"] == published["mlp_layer_types"][2:7]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["n_routed_experts"] * 8 == cfg["published"]["n_routed_experts"]
    assert cfg["source"] == rows[0]["source_url"]
    assert "8 chips share each layer" in cfg["deployment"]
    assert "published layers 2-6" in cfg["deployment"]
    assert (cfg["first_layer"], cfg["expert_first"]) == (2, 0)
    assert (cfg["index_rope_dim"], cfg["index_rope_theta"]) == (64, 10000)
    assert "the reduction is taken as the MEAN" in cfg["assumed"]["pools"]
    assert list(cfg["assumed"])[0] == "pools"
    assert {"selection", "start_and_end", "stream_mix", "kda", "indexer",
            "latent", "swiglu_limit", "routing"} <= set(cfg["assumed"])
    assert {"multi_token_prediction", "vision", "prefix_reuse",
            "long_contexts", "training"} <= set(cfg["left_out"])
    assert set(cfg["selection_check"]) == {"score_margin", "differing_share"}
    entry = LAYOUT.cell(CELL)["config_entry"]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]


def test_the_family_hands_the_builder_every_published_answer():
    family = LAYOUT.family("glm")
    cfg = _config()
    pc = family.program_config(cfg, 66_560)
    assert pc.layer_types == ("kda", "sparse_latent", "kda", "kda", "kda")
    assert (pc.first_layer, pc.first_dense, pc.num_layers) == (2, 3, 5)
    assert (pc.n_routed, pc.experts_held, pc.experts_per_token,
            pc.n_group, pc.topk_group) == (288, (0, 36), 8, 1, 1)
    assert (pc.num_heads, pc.q_lora_rank, pc.kv_lora_rank,
            pc.qk_nope_head_dim, pc.qk_rope_head_dim, pc.v_head_dim) \
        == (64, 1536, 512, 256, 0, 256)
    assert pc.indexer == dict(heads=32, dim=128, rope_dim=64, pool=4,
                              topk=2048, theta=10000.0)
    assert (pc.kda_heads, pc.kda_head_dim, pc.kda_conv_taps,
            pc.kda_lower_bound, pc.kda_decay_rank, pc.kda_gate_rank) \
        == (64, 128, 4, -5.0, 128, 128)
    assert (pc.hc_mult, pc.hc_sinkhorn_iters, pc.hc_eps) == (4, 20, 1e-6)
    assert (pc.swiglu_limit, pc.routed_scale, pc.rms_eps,
            pc.dense_width, pc.expert_width) == (10.0, 2.5, 1e-5, 12288, 2048)
    assert pc.selection_bias and pc.output_gate is None
    assert family.expert_layer_names(cfg) == [
        f"block{i}_experts" for i in range(1, 5)]
    assert family.sparse_layer_names(cfg) == ["block1_attn"]
    assert family.state_layer_names(cfg) == [
        f"block{i}_attn" for i in (0, 2, 3, 4)]
    # every published key is read, fixed or listed as ignored
    assert set(cfg) <= family.KNOWN
    for key, value in (("index_kpool", 2), ("index_kpool_compress", False),
                       ("index_kpool_always_select_tail", False),
                       ("mhc", False), ("mla_use_nope", False),
                       ("scoring_func", "softmax"), ("n_group", 8)):
        with pytest.raises(ValueError, match=key):
            family.program_config(dict(cfg, **{key: value}), 66_560)
    with pytest.raises(ValueError, match="implements no key"):
        family.program_config(dict(cfg, index_kpool_reduce="max"), 66_560)
    with pytest.raises(ValueError, match="published pattern"):
        family.program_config(dict(cfg, first_layer=3), 66_560)


def test_the_mix_is_the_issues_table():
    from benchmark import traffic

    cell = LAYOUT.cell(CELL)
    mix, cfg = cell["mix"], cell["config"]
    assert mix["kind"] == "serve_closed_routed_states_chunked"
    reqs = traffic.schedule(dict(mix, kind="serve_closed"))
    assert len(reqs) == 192 and mix["clients"] == mix["decode_slots"] == 32
    assert (mix["trace_seed"], mix["max_length"], mix["block_size"],
            mix["lead_in_s"], mix["prefill_chunk"]) \
        == (63, 66_560, 64, 40, 2048)
    assert mix["prompt"] == {"dist": "lognormal", "median": 16384,
                             "sigma": 0.6, "min": 4096, "max": 65536}
    assert mix["answer"] == {"dist": "uniform", "min": 256, "max": 768}
    assert mix["kv_dtype"] == "bfloat16"
    assert mix["check"] == [{"prompt_len": 9000, "decode_steps": 8},
                            {"prompt_len": 120, "decode_steps": 16}]
    assert max(r.prompt_len + r.answer_len for r in reqs) \
        <= mix["max_length"] <= cfg["max_position_embeddings"] == 1_048_576
    assert min(r.prompt_len for r in reqs) >= 4096
    assert max(r.prompt_len for r in reqs) <= 65_536
    lens = sorted(r.prompt_len for r in reqs)
    assert 15_000 < lens[len(lens) // 2] < 18_000
    # every context is past the budget of 2,048 rows; the check's long
    # prompt has 2,250 pools against 512, its short one is dense
    assert min(lens) > 2 * cfg["index_topk"] - 1
    assert 9000 // 4 == 2250 and 120 + 16 < cfg["index_topk"]


def test_the_new_entries_by_name():
    bench = LAYOUT.bench
    cell = LAYOUT.cell(CELL)
    assert cell["workload"]["chips"] == 1
    assert [m["name"] for m in cell["end_to_end"]] \
        == ["serve_tokens_per_s", "setup_s"]
    mine = {m["name"]: m for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(mine) == ENTRIES and len(ENTRIES) == 37
    assert {m["name"] for m in cell["per_layer"]} >= ENTRIES
    for name, m in mine.items():
        assert m["moves"] == ("setup_s" if name.startswith("setup_")
                              else "serve_tokens_per_s")
        assert LAYOUT.reader(m["name"]).read is not None
    assert [(mine[n]["unit"], mine[n]["layer"], mine[n]["source"],
             mine[n]["workloads"]) for n in NEW] == [
        ("ms", "Kernels", "device_trace", [CELL]),
        ("ms", "Paged decoder", "device_trace", [CELL]),
        ("ms", "Paged decoder", "device_trace", [CELL]),
        ("ms", "Paged decoder", "device_trace", [CELL]),
        ("%", "KV pool", "program_counter", [CELL]),
        ("ratio", "Kernels", "program_counter", [CELL]),
        ("%", "Kernels", "device_trace", [CELL])]
    serve = next(m for m in bench["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    assert CELL in serve["workloads"]
    for entry in (cell["workload"], cell["config_entry"]):
        assert len(entry["why"]) <= 200
    assert len(bench["workloads"]) == 12
    assert not any(w["chips"] == 4 for w in bench["workloads"])


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
        "name": "glm-toy", "source": "none: a toy for the CPU tests",
        "file": "benchmark/configs/glm-toy.json", "reduced": [],
        "why": "toy"})
    bench["workloads"].append({
        "name": TOY_CELL, "config": "glm-toy",
        "traffic": "serve-repositories-toy", "chips": 1, "why": "toy"})
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

    return run.run_cell(layout, TOY_CELL, SEED, 1.5, False,
                        jax.devices()[:1], time.perf_counter())


def test_toy_cell_runs_and_is_correct(result):
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert result["device"]["platform"] == "cpu"
    names = {row["name"] for row in result["checks"]}
    for tag in ("[70]", "[11]"):
        assert {"serve.routing_score_margin" + tag,
                "serve.routing_differing_share" + tag,
                "serve.selection_score_margin" + tag,
                "serve.selection_differing_share" + tag,
                "serve.paged_logits_vs_reference" + tag,
                "serve.state_rows_vs_reference" + tag,
                "serve.state_rows_coarse_share" + tag} <= names
    assert {"serve.decode_dispatches_per_step",
            "serve.attention_path_decode"} <= names
    check = result["facts"]["serve_check"]
    assert check["positions"] == 9 and check["state_layers"] == 7
    # 78 positions of which those past 15 select, 3 picks, 2 sparse layers
    assert check["selection_pairs"] == (78 - 16) * 3 * 2


def test_readers_read_the_programs_counters(layout):
    """The per-layer readers that need no trace, on hand-made readings of
    ``stats()`` at a window's two ends; the new quantities of the trace on
    a hand-made owner table; and nothing, without an error, from a program
    that lacks the counters or the scopes (the parent commit's)."""
    cfg = layout.cell(TOY_CELL)["config"]
    family = layout.family(cfg["family"])
    run = {"trace": None, "peaks": None, "config": cfg, "family": family}

    def stats(k):
        return {"moe": {"block1_experts": {
            "held": [4, 4], "steps": 10 * k, "idle_held_experts": 10 * k,
            "rows_per_held_expert": [10 * k, 0, 20 * k, 10 * k],
            "prompt_pairs_held": 40 * k}},
            "decode_steps": 10 * k, "tokens": 30 * k, "prefill_prompts": 0,
            "loop": {"prefill_chunks": 4 * k, "prefill_tokens": 60 * k,
                     "prefill_keys": 2000 * k, "prefill_keys_window": 0},
            "kv": {"blocks_read": 90 * k, "blocks_in_tables": 120 * k,
                   "block_size": 8,
                   "index": {"pools_scored": 300 * k, "pools_taken": 100 * k,
                             "rows_read": 400 * k, "rows_live": 1600 * k,
                             "dense_steps": 0, "rows_taken": 700 * k,
                             "rows_attended": 2100 * k},
                   "state": {"rows_stepped": 270 * k}}}

    run["facts"] = {"stats0": stats(0), "stats1": stats(1)}

    def read(name):
        return layout.reader(name).read(run)

    assert read("selected_rows_read_share") == 25.0
    assert read("chunk_rows_attended_over_taken") == 3.0
    assert read("expert_rows_per_step") == 1.0
    # 270 rows stepped over nine ops that keep a row: 210 are KDA states,
    # in and out, beside two sparse layers' pooled keys and taken rows
    state = 210 * 2 * counts.state_bytes(cfg)
    rest = 2 * counts.sparse_step_bytes(cfg, 1600, 400)
    assert counts.state_bytes(cfg) == 2 * 16 * 16 * 4
    assert counts.sparse_step_bytes(cfg, 1600, 400) == 400 * 32 + 400 * 64
    assert read("state_bytes_share") == pytest.approx(
        100.0 * state / (state + rest))
    traced = ("decode_select_device_ms", "chunk_kda_device_ms",
              "decode_stream_mix_device_ms", "chunk_stream_mix_device_ms",
              "sparse_latent_roofline", "decode_step_roofline",
              "prefill_chunk_mfu", "kda_state_roofline")
    for name in traced:
        assert read(name) is None            # no trace, no peaks
    run["peaks"] = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    assert family.decode_step_least_s(run) == pytest.approx(
        counts.decode_bytes_per_step(cfg, 160.0, 40.0, 21.0, 0.75) / 1e9)
    assert family.state_step_least_s(run) == pytest.approx(
        21 * 2 * counts.state_bytes(cfg) / 1e9)
    assert family.sparse_step_least_s(run) == pytest.approx(
        2 * counts.sparse_step_bytes(cfg, 160, 40) / 1e9)
    assert family.chunk_least_s(run) == pytest.approx(
        counts.chunk_flops(cfg, 60, 2000 / 4, 700, 40) / 4 / 1e12)
    run["trace"] = {"ops": [], "busy_s": 1.0, "window_s": 1.0}
    run["_owners"] = {
        "busy_s": 1.0, "unowned_s": 0.0, "scoped": True, "window_s": 1.0,
        "programs": {
            "jit__decode_step": {"count": 10, "device_s": 0.05, "rows": {
                ("KIMI_DELTA_ATTENTION", "block0_attn", "rule", "fwd"): 0.020,
                ("LATENT_ATTENTION", "block1_attn", "select", "fwd"): 0.006,
                ("LATENT_ATTENTION", "block1_attn", "attend", "fwd"): 0.004,
                ("LATENT_ATTENTION", "block1_attn", "project", "fwd"): 0.003,
                ("STREAM_MIX", "block0_res1_pre", "mix", "fwd"): 0.002,
                ("STREAM_MIX", "block0_res1", "mix", "fwd"): 0.001}},
            "jit__chunk_step": {"count": 2, "device_s": 0.2, "rows": {
                ("KIMI_DELTA_ATTENTION", "block0_attn", "rule", "fwd"): 0.06,
                ("KIMI_DELTA_ATTENTION", "block0_attn", "conv", "fwd"): 0.02,
                ("STREAM_MIX", "streams", "mix", "fwd"): 0.01,
                ("LATENT_ATTENTION", "block1_attn", "select", "fwd"): 0.03,
                ("LATENT_ATTENTION", "block1_attn", "attend", "fwd"): 0.05}}}}
    assert read("decode_select_device_ms") == pytest.approx(0.6)
    assert read("decode_stream_mix_device_ms") == pytest.approx(0.3)
    assert read("chunk_kda_device_ms") == pytest.approx(40.0)
    assert read("chunk_stream_mix_device_ms") == pytest.approx(5.0)
    assert read("chunk_select_device_ms") == pytest.approx(15.0)
    assert read("chunk_attend_device_ms") == pytest.approx(25.0)
    assert read("sparse_latent_roofline") == pytest.approx(
        100.0 * 1e3 * family.sparse_step_least_s(run) / 1.0)
    assert read("kda_state_roofline") == pytest.approx(
        100.0 * 1e3 * family.state_step_least_s(run) / 2.0)
    # the parent's programs carry no such scope and no such counter:
    # nothing, and no error
    run["_owners"]["programs"] = {"jit__decode_step": {
        "count": 10, "device_s": 0.05, "rows": {
            ("MULTIHEAD_ATTENTION", "h0_attn", "attend", "fwd"): 0.010}}}
    run["facts"] = {"stats0": {"kv": {}}, "stats1": {"kv": {}}}
    for name in NEW:
        assert read(name) is None, name
    run.update(facts={"stats0": {}, "stats1": {}}, peaks=None, trace=None)
    run.pop("_owners")
    for name in ENTRIES:
        if name.startswith(("loop_", "slot_", "setup_")):
            continue
        assert layout.reader(name).read(run) is None, name


def test_the_comparison_holds_every_arm_to_its_verdict(layout):
    """All four parts at toy size over two seeds: the sound program and the
    reference in the program's own precision inside every limit; the
    reference with its products read as float8 refused by the routing, the
    selection and the logits; with its states kept in bfloat16 refused by
    the state rows; taking half the pools that are due refused by the
    selection; with 3 Sinkhorn rounds read and held to nothing."""
    import jax

    from benchmark import control_glm, selected_states

    recs = control_glm.readings(
        layout, TOY_CELL, [SEED + 7919 * i for i in range(2)],
        jax.devices()[:1])
    cfg = layout.cell(TOY_CELL)["config"]
    v = control_glm.verdicts(recs, cfg)
    assert set(v) == {"sound", "weights_float8", "state_bfloat16",
                      "half_budget", "bfloat16", "sinkhorn_3"}
    assert not any(v["sound"]) and not any(v["bfloat16"]), (v, recs)
    assert all("state_coarse_share" in seed for seed in v["state_bfloat16"])
    assert all("selection_score_margin" in seed for seed in v["half_budget"])
    assert all("logit_error" in seed for seed in v["weights_float8"])
    for rec in recs:
        assert rec["state_bfloat16"]["state_coarse_share"] == 1.0
        assert rec["half_budget"]["selection_score_margin"] == float("inf")
    sep = control_glm.separation(recs, cfg)
    assert sep["weights_float8"]["logit_error"]["ratio"] > 2.0
    assert sep["state_bfloat16"]["state_coarse_share"]["ratio"] > 100.0
    assert set(sep["sinkhorn_3"]) == set(selected_states.limits(cfg))
    # a verdict that passes a control, or in which a part named for it
    # does not refuse it, is not sound
    ok = dict(v, weights_float8=[["logit_error", "differing_share",
                                  "selection_differing_share"]] * 2)
    assert control_glm.sound(ok, cfg)
    assert not control_glm.sound(dict(ok, state_bfloat16=[[], []]), cfg)
    assert not control_glm.sound(
        dict(ok, weights_float8=[["logit_error"]] * 2), cfg)
    assert not control_glm.sound(dict(ok, sound=[["logit_error"], []]), cfg)
    assert set(selected_states.limits(cfg)) == set(selected_states.CHECKS)


def test_the_kind_is_one_call_of_run_with(layout):
    """The kind hands ``serve_closed_plain_chunked.run_with`` the chunked
    kind's build and warm-up and ``selected_states.compare_paged``."""
    import types

    from benchmark import selected, selected_states

    seen = {}
    stub = types.SimpleNamespace(
        run_with=lambda ctx, build, warm_up, compare: seen.update(
            compare=compare, build=build, warm_up=warm_up) or {"ok": 1})
    ctx = types.SimpleNamespace(
        layout=types.SimpleNamespace(kind=lambda name: seen.update(
            kind=name) or stub))
    assert layout.kind("serve_closed_routed_states_chunked").run(ctx) \
        == {"ok": 1}
    assert seen["kind"] == "serve_closed_plain_chunked"
    assert seen["build"].__name__ == selected.build.__name__ == "build"
    assert seen["warm_up"].__name__ == "warm_up"
    assert seen["compare"].__module__ == selected_states.__name__
