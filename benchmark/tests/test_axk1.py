"""The A.X-K1 configuration, its counts, its mix and its comparison: the
hand-reckoned numbers of the configuration's cut, the mix against the
model's positions, the cell at toy size through ``run_cell`` on the CPU,
and the two-part comparison passing the sound program and refusing the
float8 control at toy size."""

import json
import os
import time

import numpy as np
import pytest

from benchmark import counts_latent_moe as counts
from benchmark.spec import ROOT, Layout
from benchmark.tests import toy

LAYOUT = Layout()
CELL = "axk1-ep16.serve-reasoning"
TOY_CELL = "axk1-toy.serve-reasoning-toy"
SEED = 2 ** 31 + 77


def _config():
    return LAYOUT.cell(CELL)["config"]


def test_counts_match_the_cuts_arithmetic():
    cfg = _config()
    # attention 101.12 M, dense MLP 396.36 M, one expert 44.04 M
    assert counts.attention_matrix_params(cfg) == 101_122_048
    assert counts.expert_params(cfg) == 44_040_192
    assert round(counts.param_count(cfg) / 1e9, 3) == 4.166
    assert counts.row_width(cfg) == 576
    assert counts.latent_bytes_per_token(cfg) == 6 * 576 * 2
    # a step with every held expert hit reads all but the embedding
    step = counts.decode_bytes_per_step(cfg, 0, 1.0)
    assert round(step / 1e9, 2) == 8.04
    # held experts 5.28 GB, attention's matrices 1.21 GB of it
    assert round(5 * 12 * counts.expert_params(cfg) * 2 / 1e9, 2) == 5.28
    assert round(6 * counts.attention_matrix_params(cfg) * 2 / 1e9, 2) == 1.21
    # half the experts idle: that much less is read
    assert step - counts.decode_bytes_per_step(cfg, 0, 0.5) == \
        5 * 6 * counts.expert_params(cfg) * 2
    assert counts.latent_attention_flops_per_row(cfg) == 64 * 1088 * 2
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    # the kernel is bound by bytes: 1152 B a row against 139 kFLOP
    assert counts.latent_attention_least_s(cfg, 1000, peaks) == \
        pytest.approx(1000 * 6 * 1152 / 819e9)


def test_counts_agree_with_the_references_shapes():
    from benchmark.reference import axk1

    cfg = _config()
    assert axk1.param_count(cfg) == counts.param_count(cfg)
    shapes = axk1.param_shapes(cfg)
    assert shapes["l1.router"] == (7168, 192)
    assert shapes["l1.experts.gate"] == (12, 7168, 2048)
    assert shapes["lm_head"] == (7168, 20480)
    assert "l0.mlp.gate" in shapes and "l0.router" not in shapes


def test_configuration_states_the_cut_and_nothing_else():
    cfg = _config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") \
            if os.path.exists(
                "/opt/skills/guides/model-configs/architectures.jsonl") \
            else open(os.devnull) as f:
        rows = [json.loads(line) for line in f if '"A.X-K1"' in line]
    if not rows:
        pytest.skip("the catalog is not on this machine")
    published = rows[0]["config"]
    for k, v in published.items():
        if k in cfg["reduced"]:
            assert cfg[k] != v and cfg["published"][k] == v
        else:
            assert cfg[k] == v, k
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert cfg["source"] == rows[0]["source_url"]
    assert "16 chips" in cfg["deployment"]
    assert set(cfg["routing_check"]) == {"score_margin", "differing_share"}


def test_the_mix_fits_the_model():
    from benchmark import traffic

    cell = LAYOUT.cell(CELL)
    mix, cfg = cell["mix"], cell["config"]
    reqs = traffic.schedule(dict(mix, kind="serve_closed"))
    assert len(reqs) == 256 and mix["clients"] == mix["decode_slots"] == 128
    assert max(r.prompt_len + r.answer_len for r in reqs) \
        <= mix["max_length"] <= cfg["max_position_embeddings"] == 131072
    assert min(r.prompt_len for r in reqs) >= 512
    assert max(r.prompt_len for r in reqs) <= max(mix["prefill_buckets"])
    assert min(r.answer_len for r in reqs) >= 1024
    assert max(r.answer_len for r in reqs) <= 3072
    assert mix["check"]["prompt_len"] <= max(mix["prefill_buckets"])


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
        "name": "axk1-toy", "source": "none: a toy for the CPU tests",
        "file": "benchmark/configs/axk1-toy.json", "reduced": [],
        "why": "toy"})
    bench["workloads"].append({
        "name": TOY_CELL, "config": "axk1-toy",
        "traffic": "serve-reasoning-toy", "chips": 1, "why": "toy"})
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
    assert {"serve.routing_score_margin", "serve.routing_differing_share",
            "serve.paged_logits_vs_reference",
            "serve.decode_dispatches_per_step",
            "serve.attention_path_decode"} <= names


def test_readers_read_the_programs_counters(layout, result):
    """The per-layer readers that need no trace, on hand-made readings of
    ``stats()`` at a window's two ends: the rows a held expert got a
    step, the load's evenness, the share of the tables read; and nothing,
    without an error, from a program that lacks the counters."""
    assert "serve_check" in result["facts"]
    from benchmark import routed_window

    cfg = layout.cell(TOY_CELL)["config"]
    run = {"trace": None, "peaks": None, "config": cfg,
           "family": layout.family(cfg["family"])}
    st = {"moe": {"block1_experts": {
        "held": [2, 4], "steps": 10, "idle_held_experts": 10,
        "rows_per_held_expert": [10, 0, 20, 10]}},
        "decode_steps": 10, "tokens": 30, "prefill_prompts": 0,
        "kv": {"blocks_read": 90, "blocks_in_tables": 120, "block_size": 8}}
    zero = {"moe": {"block1_experts": {
        "held": [2, 4], "steps": 0, "idle_held_experts": 0,
        "rows_per_held_expert": [0, 0, 0, 0]}},
        "decode_steps": 0, "tokens": 0, "prefill_prompts": 0,
        "kv": {"blocks_read": 0, "blocks_in_tables": 0, "block_size": 8}}
    run["facts"] = {"stats0": zero, "stats1": st}
    assert layout.reader("expert_rows_per_step").read(run) == 1.0
    assert layout.reader("expert_load_max_over_mean").read(run) == 2.0
    assert layout.reader("kv_blocks_read_share").read(run) == 75.0
    assert routed_window.expert_hit_share(run) == 0.75
    # 90 blocks over 30 slot-steps: each slot holds more than 2 blocks
    assert routed_window.live_tokens_per_step(run) == (90 - 30) * 8 / 10
    # a program without the counters: nothing, and no error
    run["facts"] = {"stats0": {}, "stats1": {}}
    for name in ("expert_rows_per_step",
                 "expert_load_max_over_mean",
                 "kv_blocks_read_share",
                 "decode_step_roofline",
                 "latent_attention_roofline",
                 "decode_step_device_ms"):
        assert layout.reader(name).read(run) is None


def test_the_comparison_passes_the_program_and_refuses_the_control(layout):
    """Both parts at toy size over a few seeds: the sound program inside
    every limit, the float8 reference in its place outside one at least."""
    import jax

    from benchmark import control_routed

    recs = control_routed.readings(
        layout, TOY_CELL, [SEED + 7919 * i for i in range(3)],
        jax.devices()[:1])
    cfg = layout.cell(TOY_CELL)["config"]
    limits = {"score_margin": cfg["routing_check"]["score_margin"],
              "differing_share": cfg["routing_check"]["differing_share"],
              "logit_error": cfg["limits"]["serve_logit_rel"]}
    for rec in recs:
        assert all(rec["sound"][k] <= limits[k] for k in limits), rec
        assert any(rec["control"][k] > limits[k] for k in limits), rec


def test_routing_numbers_on_a_hand_made_case():
    from benchmark import routed

    cfg = {"n_group": 2, "topk_group": 1}
    scores = np.array([[0.9, 0.8, 0.1, 0.2],      # groups (0,1) and (2,3)
                       [0.5, 0.4, 0.45, 0.1]])
    info = [{"scores": scores, "own_ids": np.array([[0, 1], [0, 1]])}]
    same = routed.routing_numbers(cfg, [np.array([[1, 0], [0, 1]])], info)
    assert same["differing"] == 0 and same["score_margin"] == 0.0
    # token 1 took expert 2 (score 0.45, group score 0.55 against 0.9) for
    # expert 1 (0.4): no shortfall of the expert's own score, and the
    # group's shortfall halved
    other = routed.routing_numbers(cfg, [np.array([[0, 1], [0, 2]])], info)
    assert other["differing"] == 1 and other["differing_share"] == 0.5
    assert other["score_margin"] == pytest.approx((0.9 - 0.55) / 2)
