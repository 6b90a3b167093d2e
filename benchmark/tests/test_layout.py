"""The layout of ``per_layer`` since PR 49: an entry a quantity, a reader
a quantity, the cells that report it a list. A case an entry, so that a
later PR's entry is held to the same rules the day it is appended; and a
ninth cell of a family of its own added to a copy of the checkout as
files and list entries alone, which then reads every quantity it joined.

(ISSUE 49 asked for the first half as ``tests/test_benchmark_layout.py``,
where tier-1 runs it; a benchmark PR may add no file outside the
benchmark's own directories, so it stands here until a PR that may moves
it: PERF.md section 7.)
"""

import ast
import hashlib
import json
import math
import os

import pytest

from benchmark.spec import Layout
from benchmark.tests import toy

LAYOUT = Layout()
BENCH = LAYOUT.bench
ENTRIES = {m["name"]: m for m in BENCH["per_layer"]}
CELLS = [w["name"] for w in BENCH["workloads"]]
READERS = os.path.join(LAYOUT.base, "layer_metrics")


def _cells_of(entry):
    """The cells that report ``entry``: its list, or without one every
    cell that reports the end-to-end metric it moves."""
    if "workloads" in entry:
        return entry["workloads"]
    return [c for c in CELLS if entry["moves"] in
            {m["name"] for m in LAYOUT.cell(c)["end_to_end"]}]


def _held_back():
    """The quantities of the chat cell, which PR 24 measured and did not
    ship: their readers wait with it."""
    with open(os.path.join(toy.HERE, "data", "chat_cell_entries.json")) as f:
        names = {m["name"] for m in json.load(f)["per_layer"]}
    return names | {"token_gap_p95_ms"}


def test_the_list_has_room_and_no_name_twice():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert len(names) <= 128              # the contract's cap
    assert not set(names) & {m["name"] for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_an_entry_names_a_quantity_its_reader_and_its_cells(name):
    entry = ENTRIES[name]
    # the reader: one file a quantity, with the text that defines it
    quantity = LAYOUT.quantity(name)
    path = os.path.join(READERS, quantity + ".py")
    assert os.path.isfile(path), f"no reader for {name}"
    with open(path) as f:
        doc = ast.get_docstring(ast.parse(f.read()))
    assert doc and "Layer:" in doc, path
    reader = LAYOUT.reader(name)
    assert callable(reader.read)
    # its cells, and the end-to-end metric it moves in each
    cells = _cells_of(entry)
    assert cells and set(cells) <= set(CELLS)
    assert len(cells) == len(set(cells))
    for cell in cells:
        assert entry["moves"] in {m["name"] for m in
                                  LAYOUT.cell(cell)["end_to_end"]}, cell
    # what the reader asks of a family, each of its cells' families answers
    asks = getattr(reader, "ASKS", None)
    if asks is not None:
        for cell in cells:
            family = LAYOUT.family(LAYOUT.cell(cell)["config"]["family"])
            assert callable(getattr(family, asks, None)), (cell, asks)
    # a name is a quantity's; one that stands apart says what forces it
    if quantity != name:
        twin = ENTRIES[quantity]
        assert (twin["moves"], twin["layer"]) != (entry["moves"],
                                                  entry["layer"]), name
        assert not set(_cells_of(twin)) & set(cells)
    if entry["name"].endswith(("_roofline", "_mfu")) or "mfu" in name:
        assert entry["unit"] == "%" and entry["better"] == "higher"


def test_every_reader_file_is_an_entrys_or_a_held_back_quantitys():
    files = {f[:-3] for f in os.listdir(READERS) if f.endswith(".py")}
    named = {LAYOUT.quantity(name) for name in set(ENTRIES) | _held_back()}
    assert files == named, files ^ named


def test_no_cell_is_without_a_share_of_its_whole_step():
    """Beside its kernels' rooflines a cell reports the whole program's
    share of the chip's peak: the decode step's, and where prompts go in
    chunks the chunk's (``mfu`` in its name); the training cell its
    step's."""
    for cell in CELLS:
        names = {m["name"] for m in LAYOUT.cell(cell)["per_layer"]}
        assert names & {"decode_step_roofline", "train_step_roofline"}, cell
        if "prefill_chunk_device_ms" in names:
            assert "prefill_chunk_mfu" in names, cell


# ---- a ninth cell, of a family of its own --------------------------------------

NINTH = "toyfam-8b.serve-ninth"
FAMILY = '''"""A toy family: nothing to build, everything the shared readers ask."""

REFERENCE = "gpt2"


def decode_step_least_s(run):
    s0, s1 = run["facts"]["stats0"], run["facts"]["stats1"]
    steps = s1["decode_steps"] - s0["decode_steps"]
    return ((run["config"]["matrix_bytes"] + 1e5 * steps)
            / run["peaks"]["hbm_bytes_per_s"])


def chunk_least_s(run):
    return run["config"]["chunk_flops"] / run["peaks"]["bf16_flops_per_s"]


def cache_bytes(run):
    return 3.0, 1.0


def state_step_least_s(run):
    return 2e-4
'''
OWN_READER = '''"""The toy family's own quantity. Layer: Kernels."""

from benchmark import owners

PROGRAM = r"_decode_step"


def read(run):
    return owners.device_ms(run, PROGRAM, kinds=("TOY_MIXER",))
'''
# what the ninth cell joins: a name appended to each of these lists
JOINED = [
    "slot_occupancy", "decode_step_device_ms", "decode_step_roofline",
    "device_idle_share", "device_owned_share", "idle_no_span_share",
    "loop_step_wall_ms", "loop_step_wall_max_ms", "loop_host_ms",
    "loop_fetch_ms", "loop_ahead_share", "prefill_chunk_window_share",
    "prefill_chunk_device_ms", "prefill_chunk_mfu", "kv_blocks_read_share",
    "state_bytes_share", "mamba_state_roofline", "decode_mamba_device_ms",
    "decode_matmul_device_ms", "decode_experts_device_ms",
    "decode_full_attention_device_ms", "chunk_attention_device_ms",
    "chunk_experts_device_ms", "expert_rows_per_step",
    "expert_load_max_over_mean", "expert_rows_computed_over_named"]


def _digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def ninth(tmp_path_factory):
    """A copy of the checkout with the ninth cell added as a later PR
    would add it: new files, and names appended to lists."""
    root = toy.make_checkout(str(tmp_path_factory.mktemp("checkout")))
    before = _digest(root)
    base = os.path.join(root, "benchmark")
    with open(os.path.join(base, "families", "toyfam.py"), "w") as f:
        f.write(FAMILY)
    with open(os.path.join(base, "configs", "toyfam-8b.json"), "w") as f:
        json.dump({"family": "toyfam", "matrix_bytes": 4.0e9,
                   "chunk_flops": 2.0e12}, f)
    with open(os.path.join(base, "traffic", "serve-ninth.json"), "w") as f:
        json.dump({"kind": "serve_closed"}, f)
    with open(os.path.join(base, "layer_metrics",
                           "toy_mixer_device_ms.py"), "w") as f:
        f.write(OWN_READER)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    n_entries = len(bench["per_layer"])
    bench["configs"].append({
        "name": "toyfam-8b", "source": "none: a toy for the CPU tests",
        "file": "benchmark/configs/toyfam-8b.json", "reduced": [],
        "why": "toy"})
    bench["workloads"].append({"name": NINTH, "config": "toyfam-8b",
                               "traffic": "serve-ninth", "chips": 1,
                               "why": "toy"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append(NINTH)
    for m in bench["per_layer"]:
        if m["name"] in JOINED:
            m["workloads"].append(NINTH)
    bench["per_layer"].append({
        "name": "toy_mixer_device_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "Kernels",
        "moves": "serve_tokens_per_s", "workloads": [NINTH]})
    with open(path, "w") as f:
        json.dump(bench, f)
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert len(bench["per_layer"]) == n_entries + 1   # a join costs no entry
    layout = Layout(root)
    cell = layout.cell(NINTH)

    def stats(k):
        return {
            "decode_steps": 10 * k, "tokens": 40 * k, "prefill_prompts": 0,
            "knobs": {"decode_slots": 4},
            "moe": {"l0_experts": {
                "held": [0, 4], "steps": 10 * k, "idle_held_experts": 10 * k,
                "rows_per_held_expert": [10 * k, 0, 20 * k, 10 * k],
                "rows_computed": 120 * k, "pairs_held": 40 * k,
                "prompt_rows_computed": 64 * k, "prompt_pairs_held": 24 * k}},
            "kv": {"blocks_read": 90 * k, "blocks_in_tables": 300 * k,
                   "block_size": 8},
            "loop": {"steps": 10 * k, "ahead": {"steps_ahead": 9 * k},
                     "prefill_chunks": 5 * k, "prefill_tokens": 200 * k,
                     "step_wall": {"buckets": {"0.25": 8 * k, "0.5": 2 * k},
                                   "max": 0.4},
                     "phase_s": {"wait": 0.0, "admit": 0.0,
                                 "prefill": 1.0 * k, "inputs": 0.1 * k,
                                 "dispatch": 0.2 * k, "fetch": 2.0 * k,
                                 "sample": 0.1 * k, "other": 0.1 * k}}}

    def rows(*owned):
        return {(kind, "l0." + kind.lower(), sub, "fwd"): s
                for kind, sub, s in owned}

    table = {"busy_s": 0.14, "unowned_s": 0.014, "scoped": True,
             "window_s": 0.4, "programs": {
                 "jit__decode_step": {"count": 10, "rows": rows(
                     ("TOY_MIXER", "", 0.01), ("MAMBA2", "rule", 0.005),
                     ("MAMBA2", "conv", 0.005), ("LINEAR", "", 0.01),
                     ("ROUTED_EXPERTS", "", 0.008),
                     ("MULTIHEAD_ATTENTION", "attend", 0.004),
                     ("MULTIHEAD_ATTENTION", "project", 0.002))},
                 "jit__chunk_step": {"count": 5, "rows": rows(
                     ("MULTIHEAD_ATTENTION", "attend", 0.02),
                     ("ROUTED_EXPERTS", "", 0.03))}}}
    trace = {"programs": {"jit__decode_step": {"count": 10, "device_s": 0.05},
                          "jit__chunk_step": {"count": 4, "device_s": 0.08},
                          "jit__chunk_step_head": {"count": 1,
                                                   "device_s": 0.02}},
             "ops": [["fusion", 0.02]], "idle_share": 0.25, "window_s": 0.4,
             "busy_s": 0.3,
             "idle_gaps": [["(no span)", 0.025], ["serving.loop.fetch", 0.05]]}
    run = {"cell": cell, "config": cell["config"], "mix": cell["mix"],
           "family": layout.family(cell["config"]["family"]),
           "facts": {"stats0": stats(0), "stats1": stats(1)},
           "trace": trace, "_owners": table,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    return layout, run


@pytest.mark.parametrize("name", JOINED + ["toy_mixer_device_ms"])
def test_the_ninth_cell_reads_every_quantity_it_joined(ninth, name):
    layout, run = ninth
    assert name in [m["name"] for m in run["cell"]["per_layer"]]
    value = layout.reader(name).read(run)
    assert isinstance(value, float) and math.isfinite(value) and value > 0
    by_hand = {
        "decode_step_device_ms": 5.0, "prefill_chunk_device_ms": 20.0,
        "decode_step_roofline": 100 * (4.0e9 + 1e6) / 819e9 / 5e-3,
        "prefill_chunk_mfu": 100 * 2.0e12 / 197e12 / 20e-3,
        "state_bytes_share": 75.0, "mamba_state_roofline": 100 * 0.2 / 0.5,
        "toy_mixer_device_ms": 1.0, "decode_mamba_device_ms": 1.0,
        "decode_matmul_device_ms": 1.8, "decode_experts_device_ms": 0.8,
        "decode_full_attention_device_ms": 0.4,
        "chunk_attention_device_ms": 4.0, "chunk_experts_device_ms": 6.0,
        "prefill_chunk_window_share": 25.0, "device_idle_share": 25.0,
        "device_owned_share": 90.0, "slot_occupancy": 100.0,
        "kv_blocks_read_share": 30.0, "loop_ahead_share": 90.0,
        "expert_rows_per_step": 1.0, "expert_load_max_over_mean": 2.0,
        "expert_rows_computed_over_named": 184 / 64}
    if name in by_hand:
        assert value == pytest.approx(by_hand[name]), name


def test_the_cells_that_were_there_read_what_they_read(ninth):
    layout, _ = ninth
    for cell in CELLS:
        assert [m["name"] for m in layout.cell(cell)["per_layer"]] == \
            [m["name"] for m in LAYOUT.cell(cell)["per_layer"]]
