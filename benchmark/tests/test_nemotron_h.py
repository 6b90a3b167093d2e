"""The Nemotron-3-Super configuration, its counts, its mix and its
comparison: the hand-reckoned numbers of the configuration's cut against
``counts_nemotron_h.py`` and the reference's shapes, the mix against the
issue's table, the new entries of ``BENCHMARK.json`` by name, the cell at
toy size through ``run_cell`` on the CPU, and the two-part comparison
passing the sound program and refusing the float8 control at toy size."""

import json
import os
import time

import pytest

from benchmark import counts_nemotron_h as counts
from benchmark.spec import Layout
from benchmark.tests import toy

LAYOUT = Layout()
CONFIG = "nemotron3-super-ep4"
CELL = "nemotron3-super-ep4.serve-agents"
TOY_CELL = "nemotron-h-toy.serve-agents-toy"
SEED = 2 ** 31 + 77
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the cell's per-layer quantities (PR 40's nineteen, under PR 49's
# names), by name: not by where ``per_layer`` ends
ENTRIES = {
    "decode_step_device_ms", "decode_step_roofline",
    "prefill_device_ms", "decode_experts_device_ms",
    "prefill_experts_device_ms", "decode_mamba_device_ms",
    "mamba_state_roofline", "expert_rows_per_step",
    "expert_load_max_over_mean",
    "expert_rows_computed_over_named", "state_bytes_share",
    "slot_occupancy", "loop_step_wall_ms",
    "loop_host_ms", "loop_fetch_ms",
    "loop_prefill_share", "device_idle_share",
    "idle_no_span_share", "device_owned_share"}


def _config():
    return LAYOUT.cell(CELL)["config"]


def test_counts_match_the_cuts_arithmetic():
    cfg = _config()
    # an M layer: in_proj 4096 x 18,560, out_proj 8192 x 4096
    assert counts.mamba_matrix_params(cfg) == 4096 * 18_560 + 8192 * 4096
    assert counts.attention_matrix_params(cfg) == 35_651_584
    assert counts.expert_params(cfg) == 2 * 1024 * 2688 == 5_505_024
    # router 2.10 M, latent down and up 2 x 4.19 M, shared 44.04 M
    assert counts.expert_layer_fixed_params(cfg) == 54_525_952
    assert round(counts.param_count(cfg) / 1e6) == 4648
    assert round(counts.param_count(cfg) * 2 / 1e9, 2) == 9.30
    # a request: 5 x (4.19 MB of state + 61 KB of tail)
    assert counts.state_bytes(cfg) == 128 * 64 * 128 * 4 == 4_194_304
    assert counts.request_bytes(cfg) == 5 * (4_194_304 + 3 * 10_240 * 2)
    assert round(counts.request_bytes(cfg) / 1e6, 1) == 21.3
    assert counts.kv_bytes_per_token(cfg) == 1024
    # a step at 128 live slots: every matrix, the states in and out, the KV
    assert round(counts.matrix_params(cfg) * 2 / 1e9, 2) == 9.03
    assert round(128 * 5 * 2 * counts.state_bytes(cfg) / 1e9, 2) == 5.37
    step = counts.decode_bytes_per_step(cfg, 128 * 1200, 128 * 5)
    assert round(step / 1e9, 1) == 14.6
    assert round(step / 819e9 * 1e3, 1) == 17.8
    # half the held experts idle: that much less is read
    assert step - counts.decode_bytes_per_step(cfg, 128 * 1200, 128 * 5,
                                               0.5) \
        == 5 * 64 * counts.expert_params(cfg) * 2
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    # the states' step is bound by bytes: 8 B a number against 5 FLOP
    assert counts.state_step_least_s(cfg, 640, peaks) == pytest.approx(
        640 * 2 * 4_194_304 / 819e9)


def test_counts_agree_with_the_references_shapes():
    from benchmark.reference import nemotron_h

    cfg = _config()
    assert nemotron_h.param_count(cfg) == counts.param_count(cfg)
    assert nemotron_h.state_bytes_per_request(cfg) \
        == counts.request_bytes(cfg)
    shapes = nemotron_h.param_shapes(cfg)
    assert shapes["l0.router"] == (4096, 512)
    assert shapes["l0.bias"] == (512,)
    assert shapes["l0.experts.up"] == (128, 1024, 2688)
    assert shapes["l0.shared.up"] == (4096, 5376)
    assert shapes["l1.w_in"] == (4096, 8192 + 10_240 + 128)
    assert shapes["l1.conv"] == (4, 10_240)
    assert shapes["l10.wk"] == (4096, 2, 128)
    assert shapes["lm_head"] == (4096, 32_768)
    assert "l0.w_in" not in shapes and "l1.router" not in shapes


def test_configuration_states_the_cut_and_nothing_else():
    cfg = _config()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f
                if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line]
    published = rows[0]["config"]
    for k, v in published.items():
        if k in cfg["reduced"]:
            assert cfg[k] != v and cfg["published"][k] == v
        else:
            assert cfg[k] == v, k
    assert cfg["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers",
        "mtp_hybrid_override_pattern"]
    assert cfg["source"] == rows[0]["source_url"]
    # the period kept is the published layers 26 to 36, the model's ratio
    first = cfg["first_layer"]
    pattern = published["hybrid_override_pattern"]
    assert pattern[first:first + 11] == cfg["hybrid_override_pattern"]
    assert [pattern.count(c) for c in "ME*"] == [40, 40, 8]
    assert [cfg["hybrid_override_pattern"].count(c) for c in "ME*"] \
        == [5, 5, 1]
    # the floors: a whole period, >= 8 experts, >= an eighth of the rows
    assert cfg["n_routed_experts"] == 128 >= 8
    assert cfg["vocab_size"] * 8 >= published["vocab_size"]
    assert "4 chips" in cfg["deployment"]
    assert set(cfg["routing_check"]) == {"score_margin", "differing_share"}
    # the three fit_* are gpt2-medium's and are not read: nothing trains
    # this configuration (``test_spec.py`` wants the four keys of every one)
    assert set(cfg["limits"]) == {"fit_first_loss_band", "fit_loss_abs",
                                  "fit_grad_rel", "serve_logit_rel"}
    entry = LAYOUT.cell(CELL)["config_entry"]
    assert entry["reduced"] == cfg["reduced"]


def test_the_mix_is_the_issues_table():
    from benchmark import traffic

    cell = LAYOUT.cell(CELL)
    mix, cfg = cell["mix"], cell["config"]
    assert mix["kind"] == "serve_closed_routed"
    reqs = traffic.schedule(dict(mix, kind="serve_closed"))
    assert len(reqs) == 512 and mix["clients"] == mix["decode_slots"] == 128
    assert (mix["max_length"], mix["block_size"], mix["lead_in_s"]) \
        == (2048, 16, 20)
    assert mix["prefill_buckets"] == [512, 768, 1024]
    assert mix["kv_dtype"] == "bfloat16"
    assert max(r.prompt_len + r.answer_len for r in reqs) \
        <= mix["max_length"] <= cfg["max_position_embeddings"]
    assert (min(r.prompt_len for r in reqs),
            max(r.prompt_len for r in reqs)) == (512, 1024)
    assert (min(r.answer_len for r in reqs),
            max(r.answer_len for r in reqs)) == (256, 768)
    assert traffic.buckets_used(mix, reqs) == [512, 768, 1024]
    # the compared prompt lies inside a bucket, with a partial chunk
    n = mix["check"]["prompt_len"]
    assert n % cfg["chunk_size"] and n not in mix["prefill_buckets"]
    # weights and pool: 9.30 GB + 129 rows of state + the blocks' KV
    pool = 129 * counts.request_bytes(cfg) \
        + (128 * 128 + 1) * 16 * counts.kv_bytes_per_token(cfg)
    assert round(pool / 1e9, 2) == 3.01
    assert 0.70 < (counts.param_count(cfg) * 2 + pool) / 16.9e9 < 0.75


def test_the_new_entries_by_name():
    bench = LAYOUT.bench
    cell = LAYOUT.cell(CELL)
    assert cell["workload"]["chips"] == 1
    assert [m["name"] for m in cell["end_to_end"]] \
        == ["serve_tokens_per_s", "setup_s"]
    mine = {m["name"]: m for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    # the cell reports these and no other quantity of a list
    assert set(mine) == ENTRIES and len(ENTRIES) == 19
    assert {m["name"] for m in cell["per_layer"]} >= ENTRIES
    for m in mine.values():
        assert m["moves"] == "serve_tokens_per_s"
        assert LAYOUT.reader(m["name"]).read is not None
    assert mine["decode_step_roofline"]["unit"] == "%"
    assert mine["mamba_state_roofline"]["unit"] == "%"


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
        "name": "nemotron-h-toy", "source": "none: a toy for the CPU tests",
        "file": "benchmark/configs/nemotron-h-toy.json", "reduced": [],
        "why": "toy"})
    bench["workloads"].append({
        "name": TOY_CELL, "config": "nemotron-h-toy",
        "traffic": "serve-agents-toy", "chips": 1, "why": "toy"})
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
            "serve.decode_dispatches_per_step",
            "serve.attention_path_decode"} <= names


def test_readers_read_the_programs_counters(layout, result):
    """The per-layer readers that need no trace, on hand-made readings of
    ``stats()`` at a window's two ends; and nothing, without an error,
    from a program that lacks the counters (the parent commit's)."""
    cfg = layout.cell(TOY_CELL)["config"]
    run = {"trace": None, "peaks": None, "config": cfg,
           "family": layout.family(cfg["family"])}

    def moe(steps, idle, rows, computed, held, p_computed, p_held):
        return {"block0_mixer": {
            "held": [4, 4], "steps": steps, "idle_held_experts": idle,
            "rows_per_held_expert": rows, "rows_computed": computed,
            "pairs_held": held, "prompt_rows_computed": p_computed,
            "prompt_pairs_held": p_held}}

    st = {"moe": moe(10, 10, [10, 0, 20, 10], 120, 40, 64, 24),
          "decode_steps": 10, "tokens": 30, "prefill_prompts": 0,
          "kv": {"blocks_read": 90, "block_size": 8,
                 "state": {"rows_stepped": 60}}}
    zero = {"moe": moe(0, 0, [0, 0, 0, 0], 0, 0, 0, 0),
            "decode_steps": 0, "tokens": 0, "prefill_prompts": 0,
            "kv": {"blocks_read": 0, "block_size": 8,
                   "state": {"rows_stepped": 0}}}
    run["facts"] = {"stats0": zero, "stats1": st}
    assert layout.reader("expert_rows_per_step").read(run) == 1.0
    assert layout.reader("expert_load_max_over_mean").read(run) == 2.0
    # (120 + 64) rows computed over (40 + 24) rows named
    assert layout.reader(
        "expert_rows_computed_over_named").read(run) == 184 / 64
    cfg = run["config"]
    state = 60 * 2 * counts.state_bytes(cfg)
    kv = 90 * 8 * counts.kv_bytes_per_token(cfg)
    assert layout.reader("state_bytes_share").read(run) \
        == pytest.approx(100 * state / (state + kv))
    # a program without the counters: nothing, and no error
    run["facts"] = {"stats0": {}, "stats1": {}}
    for name in ENTRIES:
        if name.startswith(("loop_", "slot_")):
            continue
        assert layout.reader(name).read(run) is None, name
    # one that has the older counters alone (rows named, none computed)
    old = {"moe": {"block0_mixer": {
        "held": [4, 4], "steps": 1, "idle_held_experts": 0,
        "rows_per_held_expert": [1, 1, 1, 1], "pairs_held": 4}}}
    run["facts"] = {"stats0": old, "stats1": old}
    assert layout.reader(
        "expert_rows_computed_over_named").read(run) is None


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
