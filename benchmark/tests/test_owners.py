"""The owner table on a hand-made structure with hand-worked answers, the
wire-format loader on bytes written here and on a small profile recorded
on the chip, and the readers over it."""

import json
import os

import pytest

from benchmark import owners as O
from benchmark.spec import Layout

HERE = os.path.dirname(os.path.abspath(__file__))


def _op(path):
    return {"tf_op": path + ":"} if path else {}


FC = _op("jit(train_step)/jvp(ff.LINEAR.h0.fc)/project/dot_general")
MIX = _op("jit(train_step)/transpose(jvp(ff.GATED_DELTA_NET.l0.mix))/write/"
          "while/body/dynamic_update_slice")
ADAM = _op("jit(train_step)/ff.optimizer/mul")
PLUMBING = _op("jit(train_step)/convert_element_type")
ATTEND = _op("jit(_decode_step)/ff.MULTIHEAD_ATTENTION.h0.attn/attend/"
             "dot_general")

# One chip; times in ns; the window is the host's span [0, 1000).
# jit_train_step(1) [100,300): fusion.1 [100,150) the LINEAR's product;
#   while.7 [150,250) with NO path, and inside it dus.1 [155,175) and
#   dus.2 [175,200) under the state op's ``write`` (backward) and
#   copy-done.3 [200,240) under no scope; fusion.9 [250,290) the update.
# jit__decode_step(2) [400,500): fusion.1 [400,450) (the NAME collides
#   with the step's, the owner does not), slice-done [450,480) pathless.
# jit__decode_step(3) [520,620): fusion.1 [520,570).
# jit__decode_step(4) [950,1050): cut by the window's edge, not counted.
HAND = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["%fusion.1 = f32[8]{0} fusion(%p)", 100, 50, FC],
            ["%while.7 = (s32[]) while(%t)", 150, 100, {}],
            ["%dus.1 = f32[8]{0} fusion(%a)", 155, 20, MIX],
            ["%dus.2 = f32[8]{0} fusion(%b)", 175, 25, MIX],
            ["%copy-done.3 = f32[8]{0} copy-done(%c)", 200, 40, PLUMBING],
            ["%fusion.9 = f32[8]{0} fusion(%g)", 250, 40, ADAM],
            ["%fusion.1 = f32[8]{0} fusion(%p)", 400, 50, ATTEND],
            ["%slice-done = f32[8]{0} async-done(%s)", 450, 30, {}],
            ["%fusion.1 = f32[8]{0} fusion(%p)", 520, 50, ATTEND],
            ["%fusion.1 = f32[8]{0} fusion(%p)", 950, 50, ATTEND]]},
        {"name": "XLA Modules", "events": [
            ["jit_train_step(1)", 100, 200, {}],
            ["jit__decode_step(2)", 400, 100, {}],
            ["jit__decode_step(2)", 520, 100, {}],
            ["jit__decode_step(2)", 950, 100, {}]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "main", "events": [["bench.window", 0, 1000, {}]]}]}]}

STATE = ("GATED_DELTA_NET", "l0.mix", "write", "bwd")


def test_hand_worked_table():
    t = O.owner_table(HAND)
    assert t["scoped"] and t["window_s"] == pytest.approx(1000e-9)
    step, decode = t["programs"]["jit_train_step"], \
        t["programs"]["jit__decode_step"]
    assert (step["count"], decode["count"]) == (1, 2)   # the cut one is out
    assert step["device_s"] == pytest.approx(200e-9)
    assert decode["device_s"] == pytest.approx(200e-9)
    # exclusive: the while keeps 100 - (20 + 25 + 40) = 15 ns of its own
    # and, pathless, goes to the owner of most of what it holds (45 of 85)
    assert step["rows"] == {
        ("LINEAR", "h0.fc", "project", "fwd"): pytest.approx(50e-9),
        STATE: pytest.approx((20 + 25 + 15) * 1e-9),
        ("optimizer", "", "", "fwd"): pytest.approx(40e-9),
        O.UNOWNED: pytest.approx(40e-9)}
    assert step["xla"][STATE] == {"dus": pytest.approx(45e-9),
                                  "while": pytest.approx(15e-9)}
    assert step["unowned"] == {"copy-done": [
        pytest.approx(40e-9), "jit(train_step)/convert_element_type",
        "%copy-done.3 = f32[8]{0} copy-done(%c)"]}
    # the same instruction name in another program has that program's owner
    assert decode["rows"] == {
        ("MULTIHEAD_ATTENTION", "h0.attn", "attend", "fwd"):
            pytest.approx(100e-9),
        O.UNOWNED: pytest.approx(30e-9)}
    assert decode["unowned"] == {"slice-done": [
        pytest.approx(30e-9), "", "%slice-done = f32[8]{0} async-done(%s)"]}
    # rows sum to the busy time, program by program and over the window
    for rec in t["programs"].values():
        assert sum(rec["rows"].values()) == pytest.approx(rec["busy_s"])
        assert rec["busy_s"] <= rec["device_s"]
    assert t["busy_s"] == pytest.approx((190 + 130) * 1e-9)
    assert t["unowned_s"] == pytest.approx(70e-9)


def test_a_while_of_unowned_work_stays_unowned_and_a_path_is_never_overruled():
    trace = json.loads(json.dumps(HAND))
    ops = trace["planes"][0]["lines"][0]["events"]
    ops[2][3] = ops[3][3] = PLUMBING        # the loop's body: no scope at all
    t = O.owner_table(trace)
    assert STATE not in t["programs"]["jit_train_step"]["rows"]
    assert t["programs"]["jit_train_step"]["rows"][O.UNOWNED] == \
        pytest.approx(100e-9)
    ops[1][3] = ADAM                        # a while that names its owner
    ops[2][3] = ops[3][3] = MIX
    rows = O.owner_table(trace)["programs"]["jit_train_step"]["rows"]
    assert rows[("optimizer", "", "", "fwd")] == pytest.approx(55e-9)
    assert rows[STATE] == pytest.approx(45e-9)


def test_a_trace_without_scopes_is_unscoped_and_a_window_can_be_given():
    trace = json.loads(json.dumps(HAND))
    for ev in trace["planes"][0]["lines"][0]["events"]:
        ev[3] = {}
    t = O.owner_table(trace)
    assert not t["scoped"] and t["unowned_s"] == pytest.approx(t["busy_s"])
    # without the host's span: from the first to the last operation, as
    # ``reduce.reduce_trace`` has it
    del trace["planes"][1]
    assert O.owner_table(trace)["window_s"] == pytest.approx(900e-9)
    assert set(O.owner_table(HAND, (390, 630))["programs"]) == {
        "jit__decode_step"}


def test_the_readers_sums_and_the_cli():
    run = {"trace": {}, "_owners": O.owner_table(HAND)}
    assert O.owned_share(run) == pytest.approx(100 * (1 - 70 / 320))
    assert O.device_ms(run, r"_decode_step", group="attention") == \
        pytest.approx(1e3 * 100e-9 / 2)
    assert O.device_ms(run, r"train_step", kinds=("optimizer",)) == \
        pytest.approx(1e3 * 40e-9)
    assert O.device_ms(run, r"train_step", group="state",
                       subs=("write", "conv")) == pytest.approx(1e3 * 60e-9)
    assert O.device_ms(run, r"train_step", group="matmul") == \
        pytest.approx(1e3 * 50e-9)
    assert O.device_ms(run, r"train_step", group="state",
                       subs=("rule",)) is None
    assert O.device_ms(run, r"_chunk_step", group="attention") is None
    assert O.device_ms(run, r"_decode_step", group="matmul") is None
    text = O.render(run["_owners"])
    assert "jit_train_step: 1 executions" in text
    assert "state GATED_DELTA_NET write bwd" in text
    assert "(unowned)" in text and "copy-done" in text
    (row,) = [ln for ln in text.split("\n")
              if "state GATED_DELTA_NET write bwd" in ln]
    assert row.index("<- dus") < row.index(", while")   # what it is made of
    assert "GATED_DELTA_NET l0.mix write bwd" in O.render(run["_owners"],
                                                          by="name")
    by_xla = O.render(run["_owners"], by="xla")
    (row,) = [ln for ln in by_xla.split("\n")
              if "  fusion  <- " in ln and "LINEAR" in ln]
    assert row.index("matmul LINEAR project fwd") < row.index(
        "other optimizer fwd")             # one XLA name, two owners


# ---- the loader ---------------------------------------------------------------

def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _entry(key, message):
    return _field(1, key) + _field(2, message)


def _xplane(tmp_path):
    """One device plane, one host plane, and a plane to skip, written
    field by field: statistic 1 ``tf_op`` (a string), 2 ``program_id``, 3
    ``other``; instruction 7 carries its path as a string, 8 as a
    reference to a statistic's name, 9 none."""
    stat_names = b"".join(_field(5, _entry(i, _field(1, i) + _field(2, nm)))
                          for i, nm in ((1, "tf_op"), (2, "program_id"),
                                        (3, "other"), (4, "jit(f)/ff.loss/x:")))
    meta = b"".join(_field(4, _entry(i, _field(1, i) + _field(2, nm) + st))
                    for i, nm, st in (
        (7, "%fusion.1 = f32[] fusion()",
         _field(5, _field(1, 1) + _field(5, "jit(f)/ff.LINEAR.a.b/mul:"))
         + _field(5, _field(1, 2) + _field(3, 2 ** 63 + 5))
         + _field(5, _field(1, 3) + _field(5, "dropped"))),
        (8, "%fusion.2 = f32[] fusion()",
         _field(5, _field(1, 1) + _field(7, 4))),
        (9, "%copy = f32[] copy()", b""),
        (10, "jit_f(12)", b"")))
    ops = _field(2, "XLA Ops") + _field(3, 5) + b"".join(
        _field(4, _field(1, m) + _field(2, off) + _field(3, dur)
               + _field(4, _field(1, 3) + _field(3, 1)))
        for m, off, dur in ((7, 1000, 2000), (8, 3000, 500), (9, 4000, 250)))
    mods = _field(2, "XLA Modules") + _field(3, 5) + _field(
        4, _field(1, 10) + _field(2, 0) + _field(3, 5000))
    skipped = _field(2, "Steps") + _field(4, _field(1, 10) + _field(3, 1))
    device = (_field(2, "/device:TPU:0") + _field(3, ops) + _field(3, mods)
              + _field(3, skipped) + meta + stat_names)
    host = (_field(2, "/host:CPU")
            + _field(3, _field(2, "main") + _field(3, 5)
                     + _field(4, _field(1, 1) + _field(2, 0) + _field(3, 9000))
                     + _field(4, _field(1, 2) + _field(2, 0) + _field(3, 1)))
            + _field(4, _entry(1, _field(1, 1) + _field(2, "bench.window")))
            + _field(4, _entry(2, _field(1, 2) + _field(2, "PjitFunction"))))
    other = _field(2, "/host:metadata") + _field(4, _entry(
        1, _field(1, 1) + _field(2, "jit_f(12)")))
    path = tmp_path / "plugins" / "profile" / "t" / "x.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_field(1, device) + _field(1, other) + _field(1, host))
    return str(tmp_path)


def test_the_loader_reads_the_wire_format(tmp_path):
    trace = O.load_xplane(O.reduce.find_xplane(_xplane(tmp_path)))
    device, host = trace["planes"]
    assert [ln["name"] for ln in device["lines"]] == ["XLA Ops",
                                                      "XLA Modules"]
    # the operations come as columns: an instruction once, an event an
    # index; a line's timestamp is ns, an event's offset and duration ps
    ops = device["lines"][0]
    assert [k[0].split(" ")[0] for k in ops["kinds"]] == [
        "%fusion.1", "%fusion.2", "%copy"]
    assert ops["kind"].tolist() == [0, 1, 2]
    assert ops["start_ns"].tolist() == [6.0, 8.0, 9.0]
    assert ops["duration_ns"].tolist() == [2.0, 0.5, 0.25]
    assert ops["kinds"][0][1] == {"tf_op": "jit(f)/ff.LINEAR.a.b/mul:",
                                  "program_id": 2 ** 63 + 5}
    assert ops["kinds"][1][1] == {"tf_op": "jit(f)/ff.loss/x:"}  # by reference
    assert ops["kinds"][2][1] == {}
    assert device["lines"][1]["events"] == [["jit_f(12)", 5.0, 5.0, {}]]
    assert host["lines"][0]["events"] == [["bench.window", 5.0, 9.0, {}]]
    t = O.owner_table(trace)
    assert t["programs"]["jit_f"]["rows"] == {
        ("LINEAR", "a.b", "", "fwd"): pytest.approx(2e-9),
        ("loss", "", "", "fwd"): pytest.approx(0.5e-9),
        O.UNOWNED: pytest.approx(0.25e-9)}


def test_a_profile_recorded_on_the_chip(tmp_path):
    """Three steps of a small scoped program and a second program beside
    it, traced on a v5e (``benchmark/tests/data/owners_probe.xplane.pb``,
    104 KB): ``ProfileData`` shows none of the paths this finds."""
    trace = O.load_xplane(os.path.join(HERE, "data",
                                       "owners_probe.xplane.pb"))
    t = O.owner_table(trace)
    assert set(t["programs"]) == {"jit_train_step", "jit__decode_step"}
    step = t["programs"]["jit_train_step"]
    owned = {k[:3] + (k[3],) for k in step["rows"] if k != O.UNOWNED}
    assert {("LINEAR", "h3.mlp.fc", "project", "fwd"),
            ("LINEAR", "h3.mlp.fc", "project", "bwd"),
            ("GATED_DELTA_NET", "l0.mix", "write", "fwd"),
            ("GATED_DELTA_NET", "l0.mix", "write", "bwd"),
            ("loss", "", "", "fwd")} <= owned
    # the loops carry no path of their own and lose nothing for it: what
    # stays unowned is the compiler's copies and prefetches
    assert not any(base.startswith("while") for base in step["unowned"])
    assert set(step["unowned"]) <= {"copy-done", "slice-done", "convert",
                                    "copy", "slice-start", "copy-start",
                                    "custom-call"}
    for rec in t["programs"].values():
        assert sum(rec["rows"].values()) == pytest.approx(rec["busy_s"])
        assert 0.9 * rec["device_s"] < rec["busy_s"] <= rec["device_s"]


# ---- the entries and their readers ----------------------------------------------

# PR 28's nineteen entries as PR 49 folded them: a quantity an entry, and
# the cells that report it (by name: no place in ``per_layer`` is pinned)
OWNER_ENTRIES = {
    "device_owned_share.train": ["gpt2-medium.fit-1024"],
    "attention_device_ms": ["gpt2-medium.fit-1024"],
    "matmul_device_ms": ["gpt2-medium.fit-1024"],
    "optimizer_device_ms": ["gpt2-medium.fit-1024"],
    "device_owned_share": [
        "gpt2-large.serve-offline", "axk1-ep16.serve-reasoning",
        "olmo-hybrid-pp2.serve-documents", "minicpm-sala-pp2.serve-longdocs"],
    "decode_attention_device_ms": ["gpt2-large.serve-offline",
                                   "axk1-ep16.serve-reasoning"],
    "decode_matmul_device_ms": ["gpt2-large.serve-offline",
                                "olmo-hybrid-pp2.serve-documents"],
    "decode_matmul_device_ms.reasoning": ["axk1-ep16.serve-reasoning"],
    "decode_state_write_device_ms": ["olmo-hybrid-pp2.serve-documents"],
    "chunk_select_device_ms": ["minicpm-sala-pp2.serve-longdocs"],
    "chunk_attend_device_ms": ["minicpm-sala-pp2.serve-longdocs"]}
COUNTER_ENTRIES = {
    "kv_blocks_read_share": ["gpt2-large.serve-offline"],
    "loop_ahead_share": ["gpt2-large.serve-offline",
                         "axk1-ep16.serve-reasoning"]}
ENTRIES = {**OWNER_ENTRIES, **COUNTER_ENTRIES}


def test_the_nineteen_entries_are_in_the_benchmark_by_name():
    layout = Layout()
    entries = {m["name"]: m for m in layout.bench["per_layer"]}
    layers = {m["layer"] for m in layout.bench["per_layer"]
              if m["name"] not in ENTRIES}
    assert sum(len(cells) for cells in ENTRIES.values()) == 19
    for name, cells in ENTRIES.items():
        m = entries[name]
        assert set(cells) <= set(m["workloads"]), name
        assert m["layer"] in layers          # a layer the benchmark names
        for workload in cells:
            cell = layout.cell(workload)
            assert m["moves"] == cell["end_to_end"][0]["name"]
            assert name in [e["name"] for e in cell["per_layer"]]
        assert callable(layout.reader(name).read)
    for name in OWNER_ENTRIES:
        assert entries[name]["source"] == "device_trace"
        share = name.startswith("device_owned_share")
        assert (entries[name]["unit"], entries[name]["better"]) == (
            ("%", "higher") if share else ("ms", "lower"))


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_a_reader_reports_nothing_without_a_trace_or_the_counters(name):
    read = Layout().reader(name).read
    assert read({"trace": None, "facts": {}, "cell": {
        "workload": {"name": "no-such-cell"}}}) is None
    # traced, but the profile is not there (or holds no scope): nothing
    assert read({"trace": {}, "facts": {}, "_owners": None, "cell": {
        "workload": {"name": "no-such-cell"}}}) is None


def test_the_owner_readers_read_the_table():
    layout = Layout()
    run = {"trace": {}, "facts": {}, "_owners": O.owner_table(HAND)}
    assert layout.reader("device_owned_share.train").read(run) == \
        pytest.approx(100 * (1 - 70 / 320))
    assert layout.reader("optimizer_device_ms").read(run) == \
        pytest.approx(40e-6)
    assert layout.reader("matmul_device_ms").read(run) == \
        pytest.approx(50e-6)
    assert layout.reader("decode_attention_device_ms").read(run) == \
        pytest.approx(50e-6)
    assert layout.reader("decode_state_write_device_ms").read(
        run) is None                       # the decode program has no state
    assert layout.reader("chunk_select_device_ms").read(run) is None


def test_the_counter_readers_take_the_windows_deltas():
    layout = Layout()
    facts = {"stats0": {"kv": {"blocks_read": 100, "blocks_in_tables": 400},
                        "loop": {"steps": 10, "phase_s": {},
                                 "ahead": {"steps_ahead": 8}}},
             "stats1": {"kv": {"blocks_read": 550, "blocks_in_tables": 1400},
                        "loop": {"steps": 110, "phase_s": {},
                                 "ahead": {"steps_ahead": 107}}}}
    run = {"trace": None, "facts": facts}
    assert layout.reader("kv_blocks_read_share").read(run) == \
        pytest.approx(45.0)
    assert layout.reader("loop_ahead_share").read(run) == \
        pytest.approx(99.0)
    del facts["stats0"]["loop"]["ahead"], facts["stats1"]["loop"]["ahead"]
    assert layout.reader("loop_ahead_share").read(run) is None
