"""Share of the device's busy time in the traced window that lies under a
scope of the program's, in %: 100 x (1 - ``(unowned)`` over the busy time
of all programs with an execution whole inside the window), from
``benchmark/owners.py``'s table: the device side's ``idle_no_span_share``,
which falls when code lands outside the scheme. None where nothing in
the profile is scoped (a program without the scheme). The training cell's
entry, ``device_owned_share.train``, stands apart because it moves
``train_tokens_per_s``; it is read here. Layer: Device."""

from benchmark import owners


def read(run):
    return owners.owned_share(run)
