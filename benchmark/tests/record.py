"""One traced run of a cell on the chip, and the ``run`` record its
readers were given, kept as JSON.

    python3 benchmark/tests/record.py --root <checkout> --workload <cell> \
        --seed <n> --seconds <s> --out <file.json>

``--root`` is the checkout whose ``benchmark/run.py`` runs the cell (this
one, or a parent's unpacked beside it), so that the record is that tree's
own. The file holds ``run``, cut to what the readers read (the cell's
name, the configuration, the mix, the facts, the reduced trace, the
peaks, and of ``benchmark/owners.py``'s table each program's ``count`` and
``rows``, an owner a list), and ``values``: what that checkout's readers
made of it, by the names its ``BENCHMARK.json`` gives them.
``test_records.py`` holds every reader to such records
(``data/records/``); :func:`load` gives one back as the readers want it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict

T_PROCESS = time.perf_counter()


def cut(run: Dict, table) -> Dict:
    """``run`` as JSON holds it: nothing the readers do not read."""
    facts = {}
    for k, v in run["facts"].items():
        try:
            json.dumps(v)
        except (TypeError, ValueError):
            continue
        facts[k] = v
    owners = None
    if table is not None:
        owners = {"busy_s": table["busy_s"], "unowned_s": table["unowned_s"],
                  "scoped": table["scoped"], "window_s": table["window_s"],
                  "programs": {
                      name: {"count": rec["count"],
                             "device_s": rec["device_s"],
                             "rows": [[list(o) if isinstance(o, tuple) else o,
                                       s] for o, s in rec["rows"].items()]}
                      for name, rec in table["programs"].items()}}
    return {"cell": {"workload": run["cell"]["workload"]},
            "config": run["config"], "mix": run["mix"], "facts": facts,
            "trace": run["trace"], "setup_s": run["setup_s"],
            "end_to_end": run["end_to_end"], "chips": run["chips"],
            "peaks": run["peaks"], "_owners": owners}


def load(path: str) -> Dict:
    """A record's ``{"run": ..., "values": ...}``, the owner table's rows
    keyed by tuples again."""
    with open(path) as f:
        rec = json.load(f)
    table = rec["run"].get("_owners")
    if table is not None:
        for prog in table["programs"].values():
            prog["rows"] = {tuple(o) if isinstance(o, list) else o: s
                            for o, s in prog["rows"]}
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    from benchmark import device, owners, run as bench_run
    from benchmark.spec import Layout

    kept: Dict = {}

    class Recording(Layout):
        """Hands every reader the run, and keeps what it was."""

        def reader(self, metric: str):
            mod = super().reader(metric)

            class Proxy:
                @staticmethod
                def read(run):
                    kept["run"] = run
                    return mod.read(run)
            return Proxy

    layout = Recording(root)
    chips = int(layout.cell(args.workload)["workload"]["chips"])
    devices = device.require_tpu(chips)
    device.place_compile_cache(root)
    result = bench_run.run_cell(layout, args.workload, args.seed,
                                args.seconds, True, devices, T_PROCESS)
    abandon = result.pop("_abandon_threads")
    run = kept["run"]
    record = {"run": cut(run, owners.table_of(run)),
              "values": {k: v["value"] for k, v in result["metrics"].items()},
              "names": [m["name"] for m in run["cell"]["per_layer"]],
              "correct": result["correct"], "device": result["device"]}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(record, f)
    print(json.dumps({k: v for k, v in result.items()
                      if k not in ("facts",)}), flush=True)
    sys.stderr.flush()
    if abandon:
        os._exit(0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
