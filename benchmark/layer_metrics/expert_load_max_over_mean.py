"""The fullest held expert's rows over the mean held expert's, over the
window, the mean over the expert layers: from the same counters as
``expert_rows_per_step``. 1 is an even load. Layer: Expert layer."""

from benchmark import routed_window


def read(run):
    layers = routed_window.expert_layers(run)
    if layers is None or any(sum(l["rows"]) <= 0 for l in layers):
        return None
    return sum(max(l["rows"]) * l["count"] / sum(l["rows"])
               for l in layers) / len(layers)
