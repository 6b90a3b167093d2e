"""Rows a held expert got a decode step, the mean over the held experts,
the expert layers and the window's steps: the deltas of
``stats()["moe"]``'s device-side counters. The deployment's figure is
slots x picks / experts (32 x 4 / 256 = 0.5 in the mixed-lengths cell).
Layer: Expert layer."""

from benchmark import routed_window


def read(run):
    layers = routed_window.expert_layers(run)
    if layers is None:
        return None
    return (sum(sum(l["rows"]) for l in layers)
            / sum(l["steps"] * l["count"] for l in layers))
