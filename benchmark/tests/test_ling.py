"""The Ling-3.0-flash configuration, its counts, its mix and its comparison:
the hand-reckoned numbers of the configuration's cut, the file against the
catalog's row, the mix against the issue's table, the cell's entries by
name, the cell at toy size through ``run_cell`` on the CPU, the two new
readers on a hand-made owner table, and the three-part comparison passing
the sound program and the reference in bfloat16, and refusing the float8
products and the bfloat16 states, at toy size."""

import json
import os
import time

import pytest

from benchmark import counts_ling as counts
from benchmark.spec import Layout
from benchmark.tests import toy

LAYOUT = Layout()
CELL = "ling-3.0-flash-ep8.serve-longanswers"
TOY_CELL = "ling-toy.serve-longanswers-toy"
SEED = 2 ** 31 + 58
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("decode_kda_device_ms", "kda_state_roofline")
ENTRIES = {
    "slot_occupancy", "decode_step_device_ms", "decode_step_roofline",
    "device_idle_share", "device_owned_share", "idle_no_span_share",
    "loop_step_wall_ms", "loop_host_ms", "loop_fetch_ms",
    "loop_prefill_share", "loop_ahead_share", "loop_step_wall_max_ms",
    "decode_router_device_ms", "state_bytes_share",
    "decode_state_write_device_ms", "kv_blocks_read_share",
    "kv_blocks_per_fetch", "decode_attention_device_ms",
    "decode_experts_device_ms", "decode_experts_kernel_share",
    "expert_rows_per_step", "expert_load_max_over_mean",
    "expert_rows_computed_over_named", "setup_instance_build_s",
    "setup_calibration_s"} | set(NEW)


def _config():
    return LAYOUT.cell(CELL)["config"]


def test_counts_match_the_cuts_arithmetic():
    cfg = _config()
    # a KDA mixer 52.6 M, the latent mixer 31.9 M, one expert 5.898 M
    assert counts.kda_matrix_params(cfg) == 5 * 2560 * 4096 + 2 * 2560 * 32
    assert round(counts.kda_matrix_params(cfg) / 1e6, 1) == 52.6
    assert counts.latent_matrix_params(cfg) == (
        2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 2560 * 32
        + 4096 * 2560)
    assert round(counts.latent_matrix_params(cfg) / 1e6, 1) == 32.0
    assert counts.expert_params(cfg) == 5_898_240
    parts = counts.parts(cfg)
    assert parts["dense_mlp"] == 3 * 2560 * 6144          # 47.2 M
    assert parts["router_shared"] == 6 * (2560 * 512 + 5_898_240)
    assert parts["experts"] == 6 * 64 * 5_898_240         # 377.5 M a layer
    assert parts["head"] == 2560 * 19_648
    assert round(counts.param_count(cfg) / 1e9, 2) == 2.80
    # a request: six float32 states and eighteen tails; a token: one row
    assert counts.state_bytes(cfg) == 32 * 128 * 128 * 4
    assert counts.request_bytes(cfg) == 6 * (2_097_152 + 3 * 12_288 * 2)
    assert round(counts.request_bytes(cfg) / 1e6, 2) == 13.03
    assert counts.row_width(cfg) == 576
    assert counts.kv_bytes_per_token(cfg) == 1152
    # the pool at 256 slots of 4,096 tokens: 3.35 GB of rows, 1.34 of blocks
    rows = 257 * counts.request_bytes(cfg)
    blocks = (256 * 256 + 1) * 16 * 640 * 2
    assert round(rows / 1e9, 2) == 3.35 and round(blocks / 1e9, 2) == 1.34
    share = (counts.param_count(cfg) * 2 + rows + blocks) / 16.9e9
    assert 0.60 < share < 0.62
    # a step at 256 slots: 6.44 GB of states, the experts once, the rest
    step = counts.decode_bytes_by_part(cfg, 256 * 2500, 256 * 6, 1.0)
    assert round(step["states"] / 1e9, 2) == 6.44
    assert round(step["experts"] / 1e9, 2) == 4.53
    assert round(step["latent_rows"] / 1e9, 2) == 0.74
    rest = sum(v for k, v in step.items()
               if k not in ("states", "experts", "latent_rows"))
    assert round(rest / 1e9, 2) == 0.98
    assert sum(step.values()) == counts.decode_bytes_per_step(
        cfg, 256 * 2500, 256 * 6, 1.0)
    assert step["states"] > 0.5 * (sum(step.values()) - step["experts"])
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    # the state kernel is bound by bytes: 4.2 MB a row against 3.7 MFLOP
    assert counts.state_step_least_s(cfg, 1536, peaks) == pytest.approx(
        1536 * 2 * 2_097_152 / 819e9)


def test_counts_agree_with_the_references_shapes():
    from benchmark.reference import ling

    cfg = _config()
    assert ling.param_count(cfg) == counts.param_count(cfg)
    shapes = ling.param_shapes(cfg)
    assert shapes["l1.router"] == (2560, 512) and shapes["l1.bias"] == (512,)
    assert shapes["l1.experts.gate"] == (64, 2560, 768)
    assert shapes["l0.mlp.gate"] == (2560, 6144) and "l0.router" not in shapes
    assert shapes["l0.wf"] == (2560, 4096) and shapes["l0.wg"] == (2560, 32)
    assert shapes["l0.dt_bias"] == (4096,) and shapes["l0.a_log"] == (32,)
    assert shapes["l0.conv"] == (4, 3 * 4096)
    assert shapes["l4.wq"] == (2560, 32 * 192)       # published layer 5
    assert shapes["l4.wkv_a"] == (2560, 576) and "l4.wf" not in shapes
    assert shapes["lm_head"] == (2560, 19_648)
    assert [m for m, _ in ling.layer_kinds(cfg)] == [
        "kda", "kda", "kda", "kda", "latent", "kda", "kda"]
    assert [d for _, d in ling.layer_kinds(cfg)] == [True] + [False] * 6


def test_configuration_states_the_cut_and_nothing_else():
    cfg = _config()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if '"Ling-3.0-flash"' in line]
    published = rows[0]["config"]
    for k, v in published.items():
        if k in cfg["reduced"]:
            assert cfg[k] != v and cfg["published"][k] == v
        else:
            assert cfg[k] == v, k
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size", "num_nextn_predict_layers"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"]) == (7, 64, 19_648, 0)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["source"] == rows[0]["source_url"]
    assert "8 chips share each layer; stages of 7 layers" in cfg["deployment"]
    assert (cfg["first_layer"], cfg["expert_first"]) == (1, 0)
    assert set(cfg["routing_check"]) == {"score_margin", "differing_share"}
    assert {"layer_pattern", "decay", "output_gate", "qk_norm",
            "selection_bias"} <= set(cfg["assumed"])
    assert {"multi_token_prediction", "chunked_prefill_and_long_contexts",
            "swiglu_clamps"} <= set(cfg["left_out"])
    entry = LAYOUT.cell(CELL)["config_entry"]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]


def test_the_family_hands_the_builder_every_published_answer():
    family = LAYOUT.family("ling")
    cfg = _config()
    pc = family.program_config(cfg, 4096)
    assert pc.layer_types == ("kda",) * 4 + ("latent",) + ("kda",) * 2
    assert (pc.first_layer, pc.first_dense, pc.num_layers) == (1, 2, 7)
    assert (pc.n_routed, pc.experts_held, pc.experts_per_token,
            pc.n_group, pc.topk_group) == (512, (0, 64), 8, 8, 4)
    assert pc.q_lora_rank is None and pc.output_gate == "head"
    assert pc.selection_bias and pc.rope_interleaved
    assert (pc.kda_head_dim, pc.kda_conv_taps, pc.kda_lower_bound) \
        == (128, 4, -5.0)
    assert (pc.rope_theta, pc.routed_scale, pc.rms_eps) == (6e6, 2.5, 1e-6)
    assert family.expert_layer_names(cfg) == [
        f"block{i}_experts" for i in range(1, 7)]
    # every published key is read, fixed or listed as ignored
    assert set(cfg) <= family.KNOWN
    with pytest.raises(ValueError, match="non-zero SwiGLU limit"):
        family.program_config(dict(cfg, first_layer=30), 4096)
    with pytest.raises(ValueError, match="implements no key"):
        family.program_config(dict(cfg, sliding_window=128), 4096)


def test_the_mix_is_the_issues_table():
    from benchmark import traffic

    cell = LAYOUT.cell(CELL)
    mix, cfg = cell["mix"], cell["config"]
    assert mix["kind"] == "serve_closed_routed_states"
    reqs = traffic.schedule(dict(mix, kind="serve_closed"))
    assert len(reqs) == 512 and mix["clients"] == mix["decode_slots"] == 256
    assert (mix["trace_seed"], mix["max_length"], mix["block_size"],
            mix["lead_in_s"]) == (58, 4096, 16, 30)
    assert mix["prompt"] == {"dist": "uniform", "min": 512, "max": 1024}
    assert mix["answer"] == {"dist": "uniform", "min": 1024, "max": 3072}
    assert mix["kv_dtype"] == "bfloat16"
    assert mix["prefill_buckets"] == [512, 768, 1024]
    assert mix["check"] == {"prompt_len": 700, "decode_steps": 8}
    assert max(r.prompt_len + r.answer_len for r in reqs) \
        <= mix["max_length"] <= cfg["max_position_embeddings"] == 262_144
    assert min(r.prompt_len for r in reqs) >= 512
    assert max(r.prompt_len for r in reqs) <= max(mix["prefill_buckets"])
    assert min(r.answer_len for r in reqs) >= 1024
    assert max(r.answer_len for r in reqs) <= 3072
    assert traffic.bucket_for(mix["prefill_buckets"], 700) == 768


def test_the_new_entries_by_name():
    bench = LAYOUT.bench
    cell = LAYOUT.cell(CELL)
    assert cell["workload"]["chips"] == 1
    assert [m["name"] for m in cell["end_to_end"]] \
        == ["serve_tokens_per_s", "setup_s"]
    mine = {m["name"]: m for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    # the cell reports these and no other quantity of a list
    assert set(mine) == ENTRIES and len(ENTRIES) == 27
    assert {m["name"] for m in cell["per_layer"]} >= ENTRIES
    for name, m in mine.items():
        assert m["moves"] == ("setup_s" if name.startswith("setup_")
                              else "serve_tokens_per_s")
        assert LAYOUT.reader(m["name"]).read is not None
    # the two this cell brings, its alone (no count of the list's length
    # or of the cells: the next cell appends to both)
    assert [(mine[n]["unit"], mine[n]["layer"], mine[n]["source"],
             mine[n]["workloads"]) for n in NEW] == [
        ("ms", "Paged decoder", "device_trace", [CELL]),
        ("%", "Kernels", "device_trace", [CELL])]
    # shares whose readers count by another family's keys, and the prefill
    # program's two, which the traced 3 s after a 30 s lead-in do not hold
    # (the lead-in's first job ends at 37-40 s): not this cell's
    for name in ("latent_attention_roofline", "gated_delta_roofline",
                 "prefill_device_ms", "prefill_experts_device_ms"):
        assert CELL not in next(m for m in bench["per_layer"]
                                if m["name"] == name)["workloads"]
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
        "name": "ling-toy", "source": "none: a toy for the CPU tests",
        "file": "benchmark/configs/ling-toy.json", "reduced": [],
        "why": "toy"})
    bench["workloads"].append({
        "name": TOY_CELL, "config": "ling-toy",
        "traffic": "serve-longanswers-toy", "chips": 1, "why": "toy"})
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
    assert {"serve.routing_score_margin", "serve.routing_differing_share",
            "serve.paged_logits_vs_reference",
            "serve.state_rows_vs_reference", "serve.state_rows_coarse_share",
            "serve.decode_dispatches_per_step",
            "serve.attention_path_decode"} <= names
    assert result["facts"]["serve_check"]["positions"] == 9
    assert result["facts"]["serve_check"]["state_layers"] == 4


def test_readers_read_the_programs_counters(layout):
    """The per-layer readers that need no trace, on hand-made readings of
    ``stats()`` at a window's two ends; the two new quantities on a
    hand-made owner table; and nothing, without an error, from a program
    that lacks the counters or the scopes (the parent commit's)."""
    cfg = layout.cell(TOY_CELL)["config"]
    family = layout.family(cfg["family"])
    run = {"trace": None, "peaks": None, "config": cfg, "family": family}

    def stats(k):
        return {"moe": {"block1_experts": {
            "held": [4, 4], "steps": 10 * k, "idle_held_experts": 10 * k,
            "rows_per_held_expert": [10 * k, 0, 20 * k, 10 * k]}},
            "decode_steps": 10 * k, "tokens": 30 * k, "prefill_prompts": 0,
            "kv": {"blocks_read": 90 * k, "blocks_in_tables": 120 * k,
                   "block_size": 8,
                   "state": {"rows_stepped": 120 * k}}}

    run["facts"] = {"stats0": stats(0), "stats1": stats(1)}

    def read(name):
        return layout.reader(name).read(run)

    assert read("expert_rows_per_step") == 1.0
    assert read("kv_blocks_read_share") == 75.0
    # 120 (slot, layer) states in and out beside 90 blocks of latent rows
    # over the toy's two latent layers
    state = 120 * 2 * counts.state_bytes(cfg)
    rows = 90 * 8 * counts.kv_bytes_per_token(cfg)
    assert counts.state_bytes(cfg) == 2 * 64 * 64 * 4
    assert counts.kv_bytes_per_token(cfg) == 2 * 40 * 2
    assert read("state_bytes_share") == pytest.approx(
        100.0 * state / (state + rows))
    for name in NEW + ("decode_step_roofline",):
        assert read(name) is None            # no trace, no peaks
    run["peaks"] = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    assert family.decode_step_least_s(run) == pytest.approx(
        counts.decode_bytes_per_step(cfg, 48.0, 12.0, 0.75) / 1e9)
    assert family.state_step_least_s(run) == pytest.approx(
        12 * 2 * counts.state_bytes(cfg) / 1e9)
    # the owner table of a traced window: 3 ms a step under the KDA ops,
    # 2 of them under ``rule``
    run["trace"] = {"ops": [], "busy_s": 1.0, "window_s": 1.0}
    run["_owners"] = {"busy_s": 1.0, "unowned_s": 0.0, "scoped": True,
                      "window_s": 1.0, "programs": {"jit__decode_step": {
                          "count": 10, "device_s": 0.05, "rows": {
                              ("KIMI_DELTA_ATTENTION", "block0_attn",
                               "rule", "fwd"): 0.020,
                              ("KIMI_DELTA_ATTENTION", "block0_attn",
                               "gate", "fwd"): 0.006,
                              ("KIMI_DELTA_ATTENTION", "block2_attn",
                               "out", "fwd"): 0.004,
                              ("LATENT_ATTENTION", "block1_attn",
                               "attend", "fwd"): 0.010}}}}
    assert read("decode_kda_device_ms") == pytest.approx(3.0)
    assert read("kda_state_roofline") == pytest.approx(
        100.0 * 1e3 * family.state_step_least_s(run) / 2.0)
    assert read("decode_attention_device_ms") == pytest.approx(1.0)
    # the parent's programs carry no such scope: nothing, and no error
    run["_owners"]["programs"]["jit__decode_step"]["rows"] = {
        ("LATENT_ATTENTION", "block1_attn", "attend", "fwd"): 0.010}
    for name in NEW:
        assert read(name) is None
    # a program without any of the counters: nothing, and no error
    run.update(facts={"stats0": {}, "stats1": {}}, peaks=None, trace=None)
    run.pop("_owners")
    for name in ENTRIES:
        if name.startswith(("loop_", "slot_", "setup_")):
            continue
        assert layout.reader(name).read(run) is None, name


def test_the_comparison_holds_every_arm_to_its_verdict(layout):
    """All three parts at toy size over a few seeds: the sound program and
    the reference in the program's own precision inside every limit; the
    reference with its products read as float8 outside the logits' limit;
    the reference with its states kept in bfloat16 INSIDE the logits'
    limit, under the sound program's own reading, and refused by the
    state rows."""
    import jax

    from benchmark import control_ling, routed_states

    recs = control_ling.readings(
        layout, TOY_CELL, [SEED + 7919 * i for i in range(3)],
        jax.devices()[:1])
    cfg = layout.cell(TOY_CELL)["config"]
    v = control_ling.verdicts(recs, cfg)
    assert control_ling.sound(v), (v, recs)
    assert set(v) == {"sound", "weights_float8", "state_bfloat16", "bfloat16"}
    assert all("logit_error" in seed for seed in v["weights_float8"])
    assert all("state_coarse_share" in seed and "logit_error" not in seed
               for seed in v["state_bfloat16"])
    for rec in recs:
        assert rec["state_bfloat16"]["logit_error"] < rec["sound"]["logit_error"]
        assert rec["state_bfloat16"]["state_coarse_share"] == 1.0
    sep = control_ling.separation(recs)
    assert sep["weights_float8"]["logit_error"]["ratio"] > 2.0
    assert sep["weights_float8"]["state_error"]["ratio"] > 2.0
    assert sep["state_bfloat16"]["state_coarse_share"]["ratio"] > 100.0
    # a verdict that passes a lower precision is not sound
    assert not control_ling.sound(dict(v, state_bfloat16=[[], [], []]))
    assert set(routed_states.limits(cfg)) == set(routed_states.CHECKS)


def test_state_numbers_read_what_a_state_was_kept_in():
    import jax.numpy as jnp
    import numpy as np

    from benchmark import routed_states

    rng = np.random.default_rng(58)
    want = [rng.standard_normal((2, 16, 64)).astype(np.float32)
            for _ in range(3)]
    n = routed_states.state_numbers(want, want)
    assert n["state_error"] == 0.0 and n["state_coarse_share"] < 1e-3
    kept = [np.asarray(jnp.asarray(s).astype(jnp.bfloat16), np.float32)
            for s in want]
    n = routed_states.state_numbers(kept, want)
    assert n["state_coarse_share"] == 1.0 and 1e-3 < n["state_error"] < 4e-3
    half = [s.astype(np.float16).astype(np.float32) for s in want]
    assert 0.05 < routed_states.state_numbers(half, want)[
        "state_coarse_share"] < 0.25
    # the largest over the layers; zeros are not counted as coarse
    off = [want[0], want[1] * 1.5, np.zeros_like(want[2])]
    n = routed_states.state_numbers(off, want)
    assert n["state_error"] == pytest.approx(1.0)
    assert n["state_coarse_share"] < 1e-3
    with pytest.raises(ValueError, match="2 states against 3"):
        routed_states.state_numbers(want[:2], want)


def test_the_kind_is_one_call_of_run_with(layout):
    """The kind hands ``serve_closed_plain_chunked.run_with`` the routed
    kind's build and warm-up and ``routed_states.compare_paged``."""
    import types

    from benchmark import routed_states

    seen = {}
    stub = types.SimpleNamespace(
        run_with=lambda ctx, build, warm_up, compare: seen.update(
            compare=compare, build=build, warm_up=warm_up) or {"ok": 1})
    ctx = types.SimpleNamespace(
        layout=types.SimpleNamespace(kind=lambda name: seen.update(
            kind=name) or stub))
    assert layout.kind("serve_closed_routed_states").run(ctx) == {"ok": 1}
    assert seen["kind"] == "serve_closed_plain_chunked"
    assert seen["compare"] is routed_states.compare_paged
