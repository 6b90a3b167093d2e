"""Same record in, same number out: every reader held to one traced run
of each cell on the chip.

``data/records/<cell>.json`` is the ``run`` record of one ``--trace 1``
run of the cell on a TPU v5e, as ``record.py`` cut it (the facts, the
reduced trace, the owner table's rows, the configuration, the peaks),
with ``values``: what the benchmark's readers made of it then, a null
where a reader found nothing. PR 49 took the records on the parent's
tree, read them with its 128 readers, and folded those to a reader a
quantity: the numbers here are the parent's, under the names PR 49 gave
them (PERF.md section 3 has the table), and a reader that is changed has
to give them back to the last digit. The quantities a cell joined since
its record was taken stand in ``joined``, with what the reader made of
the record on the day.
"""

import json
import os

import pytest

from benchmark.spec import Layout
from benchmark.tests import record

LAYOUT = Layout()
RECORDS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "records")
_LOADED = {}


def _record(cell):
    if cell not in _LOADED:
        rec = record.load(os.path.join(RECORDS, cell + ".json"))
        rec["run"]["family"] = LAYOUT.family(rec["run"]["config"]["family"])
        rec["run"]["cell"] = dict(LAYOUT.cell(cell), **rec["run"]["cell"])
        _LOADED[cell] = rec
    return _LOADED[cell]


def _cases():
    out = []
    for f in sorted(os.listdir(RECORDS)) if os.path.isdir(RECORDS) else []:
        with open(os.path.join(RECORDS, f)) as fh:
            rec = json.load(fh)
        for key in ("values", "joined"):
            out += [(f[:-5], key, name) for name in rec.get(key, {})]
    return out


CASES = _cases()


def test_every_cell_has_a_record_of_every_quantity_it_reports():
    cells = [w["name"] for w in LAYOUT.bench["workloads"]]
    assert sorted({c for c, _, _ in CASES}) == sorted(cells)
    for cell in cells:
        have = {n for c, _, n in CASES if c == cell}
        assert have == {m["name"] for m in LAYOUT.cell(cell)["per_layer"]}


@pytest.mark.parametrize("cell,key,name", CASES)
def test_a_reader_gives_the_recorded_number_back(cell, key, name):
    rec = _record(cell)
    assert rec["correct"] is True and rec["device"]["platform"] == "tpu"
    value = LAYOUT.reader(name).read(rec["run"])
    want = rec[key][name]
    if want is None:
        assert value is None
    else:
        assert value is not None and float(value) == want  # to the last digit
        if name.endswith("_roofline") or "mfu" in name:
            assert 0 < value <= 100
