"""Share of the ``fit`` calls' wall time that steps waited for input
(``fit_profile["epochs"][i]["input_wait_s"]`` over ``wall_s``), in %.
Layer: Input pipeline."""


def read(run):
    epochs = run["facts"].get("epochs")
    if not epochs:
        return None
    wall = sum(e["wall_s"] for e in epochs)
    return 100.0 * sum(e["input_wait_s"] for e in epochs) / wall
