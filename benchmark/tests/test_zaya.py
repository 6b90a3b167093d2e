"""The ZAYA1-8B configuration, its counts, its mix and its comparison: the
hand-reckoned numbers of the configuration's cut against
``counts_zaya.py`` and the reference's shapes, the configuration's file
against the catalog's row, the mix against the issue's table, the new
entries of ``BENCHMARK.json`` by name, the cell at toy size through
``run_cell`` on the CPU (both items of its ``check`` list compared), the
readers on hand-made counters and on the cell's record, and the two-part
comparison passing the sound program and refusing the float8 control at
toy size."""

import json
import os
import time

import pytest

from benchmark import counts_zaya as counts
from benchmark.spec import Layout
from benchmark.tests import record, toy

LAYOUT = Layout()
CONFIG = "zaya1-8b-pp2"
CELL = "zaya1-8b-pp2.serve-chains"
TOY_CELL = "zaya-toy.serve-chains-toy"
SEED = 2 ** 31 + 77
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"decode_cca_mix_device_ms", "decode_cca_attend_device_ms",
       "cca_attention_roofline", "decode_router_device_ms"}
# the cell's per-layer quantities, by name: the joined ones and the four
# this configuration brought
ENTRIES = NEW | {
    "slot_occupancy", "decode_step_device_ms", "decode_step_roofline",
    "device_idle_share", "device_owned_share", "idle_no_span_share",
    "loop_step_wall_ms", "loop_step_wall_max_ms", "loop_host_ms",
    "loop_fetch_ms", "kv_blocks_read_share", "state_bytes_share",
    "state_rows_carried_share", "expert_rows_per_step",
    "expert_load_max_over_mean", "expert_rows_computed_over_named",
    "decode_experts_device_ms", "chunk_experts_device_ms",
    "prefill_chunk_device_ms", "prefill_chunk_mfu",
    "prefill_chunk_window_share"}


def _config():
    return LAYOUT.cell(CELL)["config"]


def test_counts_match_the_cuts_arithmetic():
    """The issue's arithmetic, to the parameter."""
    cfg = _config()
    e, d = 2048, 128
    cca = (e * 1024 * 2 + e * 256 + 2 * e * 128    # Wq, Wo, Wk, Wv1, Wv2
           + 10 * 2 * d * d)                        # the grouped convolution
    assert counts.attention_matrix_params(cfg) == cca == 5570560
    router = e * 256 + 2 * 256 * 256 + 256 * 16
    assert counts.router_matrix_params(cfg) == router == 659456
    assert counts.expert_params(cfg) == 3 * e * 2048 == 12582912
    vectors = 10 * e + 4 * 1280 + 2 + 5 * 256 + 16
    assert counts.layer_vector_params(cfg, 1) == vectors == 26898
    assert counts.layer_vector_params(cfg, 0) == vectors - 256
    layer = cca + router + 16 * 12582912 + vectors
    assert layer == 207583506                       # "207.6 M a layer"
    table = 262272 * e
    total = 20 * layer - 256 + table + e
    assert counts.param_count(cfg) == total == 4688804968    # "4.689 B"
    assert round(total * 2 / 1e9, 2) == 9.38
    # what a token and a request keep
    assert counts.kv_row_bytes(cfg) == 1024
    assert counts.kv_bytes_per_token(cfg) == 20480
    assert counts.state_bytes(cfg) == 5376
    assert counts.request_bytes(cfg, 4608) == 4608 * 20480 + 20 * 5376
    # the cell's pool beside the weights: over a quarter of the chip
    pool = (48 * 72 + 1) * 64 * 20480 + 49 * 20 * 5376
    assert round((total * 2 + pool) / 1e9, 1) == 13.9
    # a decode step at the cell's mid-life: 48 slots of 1,970 tokens
    step = counts.decode_bytes_per_step(cfg, 48 * 1970, 48 * 20, 1.0)
    assert round(step / 1e9, 2) == 11.32
    assert round(1e3 * step / 819e9, 1) == 13.8     # ms, by bytes
    assert round(counts.attend_bytes_per_step(cfg, 48 * 1970) / 1e9, 2) \
        == 1.94
    assert round(20 * 16 * 12582912 * 2 / 1e9, 2) == 8.05


def test_counts_agree_with_the_references_shapes():
    ref = LAYOUT.reference("zaya")
    cfg = _config()
    assert ref.param_count(cfg) == counts.param_count(cfg)
    shapes = ref.param_shapes(cfg)
    assert shapes["embed"] == (262272, 2048) and "lm_head" not in shapes
    assert shapes["l3.conv1"] == (10, 2, 128, 128)
    assert "l0.router.depth" not in shapes and "l1.router.depth" in shapes
    with open(os.path.join(toy.HERE, "data", "configs",
                           "zaya-toy.json")) as f:
        small = json.load(f)
    assert ref.param_count(small) == counts.param_count(small)


def test_configuration_states_the_cut_and_nothing_else():
    """Every number of the catalog's row under the same key, but for the
    keys ``reduced`` names; the family's own keys beside them."""
    cfg = _config()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B")
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 20 == len(cfg["layer_types"])
    assert set(cfg["layer_types"]) == {"hybrid"}
    assert cfg["first_layer"] == 0 and cfg["family"] == "zaya"
    assert "two chips" in cfg["deployment"] and "stage 0" in cfg["deployment"]
    assert {"convolutions", "qk_mean", "value_shift", "qk_length", "rotary",
            "router", "choice", "residual", "skip_expert"} <= set(
        cfg["assumed"])
    entry = LAYOUT.cell(CELL)["config_entry"]
    assert entry["reduced"] == cfg["reduced"]
    # the head reads its last rows only: more than any check item needs
    assert cfg["reference_head_rows"] > max(
        i["decode_steps"] for i in LAYOUT.cell(CELL)["mix"]["check"])


def test_the_mix_is_the_issues_table():
    from benchmark import traffic

    mix = LAYOUT.cell(CELL)["mix"]
    assert mix["kind"] == "serve_closed_routed_chunked"
    assert (mix["clients"], mix["decode_slots"], mix["jobs"],
            mix["trace_seed"]) == (48, 48, 192, 50)
    assert mix["prompt"] == {"dist": "lognormal", "median": 1024,
                             "sigma": 0.7, "min": 256, "max": 2560}
    assert mix["answer"] == {"dist": "uniform", "min": 1024, "max": 2048}
    assert (mix["max_length"], mix["block_size"], mix["prefill_chunk"],
            mix["kv_dtype"], mix["lead_in_s"]) == (4608, 64, 2048,
                                                   "bfloat16", 30)
    assert mix["kv_divergence_budget"] == 0.25
    assert mix["check"] == [{"prompt_len": 2500, "decode_steps": 8},
                            {"prompt_len": 700, "decode_steps": 4}]
    reqs = traffic.schedule(dict(mix, kind="serve_closed"))
    prompts = [r.prompt_len for r in reqs]
    assert len(reqs) == 192 and min(prompts) == 256 and max(prompts) == 2560
    assert max(r.prompt_len + r.answer_len for r in reqs) <= 4608
    # a sixth of the prompts take a second chunk
    assert sum(p > 2048 for p in prompts) == 31
    assert round(sum(prompts) / 192) == 1199


def test_the_new_entries_by_name():
    bench = LAYOUT.bench
    cell = LAYOUT.cell(CELL)
    assert cell["workload"]["chips"] == 1
    assert [m["name"] for m in cell["end_to_end"]] \
        == ["serve_tokens_per_s", "setup_s"]
    mine = {m["name"]: m for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(mine) == ENTRIES and len(ENTRIES) == 25
    for m in mine.values():
        assert m["moves"] == "serve_tokens_per_s"
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["source"] == "device_trace"
    assert [m["name"] for m in bench["per_layer"][-4:]] == [
        "decode_cca_mix_device_ms", "decode_cca_attend_device_ms",
        "cca_attention_roofline", "decode_router_device_ms"]
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG
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
        "name": "zaya-toy", "source": "none: a toy for the CPU tests",
        "file": "benchmark/configs/zaya-toy.json", "reduced": [],
        "why": "toy"})
    bench["workloads"].append({
        "name": TOY_CELL, "config": "zaya-toy",
        "traffic": "serve-chains-toy", "chips": 1, "why": "toy"})
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
    for n in (39, 11):
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
    run = {"trace": None, "config": cfg,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "family": layout.family(cfg["family"])}

    def moe(steps, idle, rows, computed, held, p_computed, p_held):
        return {"block1_experts": {
            "held": [0, 4], "steps": steps, "idle_held_experts": idle,
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
                       "state": {"rows_stepped": 90 * k,
                                 "rows_started": 12 * k,
                                 "rows_carried": 4 * k}},
                "loop": {"prefill_chunks": 4 * k, "prefill_tokens": 50 * k,
                         "prefill_keys": 900 * k,
                         "prefill_keys_window": 0}}

    run["facts"] = {"stats0": stats(0), "stats1": stats(1)}

    def read(name):
        return layout.reader(name).read(run)

    assert read("kv_blocks_read_share") == 30.0
    assert read("expert_rows_per_step") == 1.0
    assert read("expert_load_max_over_mean") == 2.0
    assert read("expert_rows_computed_over_named") == 184 / 64
    assert read("state_rows_carried_share") == 25.0
    family = run["family"]
    state = 90 * 2 * counts.state_bytes(cfg)
    rest = 90 * 8 * counts.kv_bytes_per_token(cfg)
    assert family.cache_bytes(run) == (state, rest)
    assert read("state_bytes_share") == 100.0 * state / (state + rest)
    live = (90 - 30) * 8 / 10            # counted low from the blocks read
    assert family.cca_attend_least_s(run) == pytest.approx(
        counts.attend_bytes_per_step(cfg, live) / 819e9)
    assert family.decode_step_least_s(run) == pytest.approx(
        counts.decode_bytes_per_step(cfg, live, 9.0, 0.75) / 819e9)
    assert family.chunk_least_s(run) == pytest.approx(
        counts.chunk_flops(cfg, 50, 24, 900) / 4 / 197e12)
    # the traced ones read nothing without a trace
    for name in sorted(NEW) + ["decode_step_roofline", "prefill_chunk_mfu"]:
        assert read(name) is None
    # a program without the counters: nothing, and no error
    run["facts"] = {"stats0": {}, "stats1": {}}
    for name in ENTRIES:
        if name.startswith(("loop_", "slot_")):
            continue
        assert layout.reader(name).read(run) is None, name


def test_every_reader_reads_the_cells_record():
    """The record of one traced run of the cell on the chip
    (``data/records/``, which ``test_records.py`` holds every reader to,
    to the last digit): every quantity the cell reports reads non-null
    there, the four new ones among them, and no share passes 100 %."""
    path = os.path.join(toy.HERE, "data", "records", CELL + ".json")
    rec = record.load(path)
    assert rec["correct"] is True and rec["device"]["platform"] == "tpu"
    assert set(rec["values"]) == {m["name"] for m in
                                  LAYOUT.cell(CELL)["per_layer"]}
    for name in ENTRIES:
        assert rec["values"][name] is not None, name
    for name in ("cca_attention_roofline", "decode_step_roofline",
                 "prefill_chunk_mfu"):
        assert 0 < rec["values"][name] < 100, name


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
