"""Share of the window's decode steps whose held experts ran as the
grouped kernel (the matrices of the experts the step's live rows named,
and no others) and not in the dense form (every held expert's), in %,
summed over the expert layers: the deltas of ``stats()["moe"]``'s
``kernel_steps`` over those of its ``steps``. A decode program that is the
kernel by its shapes reads 100 and a dense one 0; one whose steps choose
(``form_decode`` ``"counted"``: each counts the held experts its live rows
name, against 0.9 of those held) reads how many chose the kernel. None
from a program without the counter. Layer: Expert layer."""


def read(run):
    f = run["facts"]
    m0 = (f.get("stats0") or {}).get("moe")
    m1 = (f.get("stats1") or {}).get("moe")
    if not m0 or not m1:
        return None
    kernel = steps = 0
    for name, b in m1.items():
        a = m0.get(name)
        if a is None or "kernel_steps" not in a or "kernel_steps" not in b:
            return None
        kernel += b["kernel_steps"] - a["kernel_steps"]
        steps += b["steps"] - a["steps"]
    return 100.0 * kernel / steps if steps > 0 else None
