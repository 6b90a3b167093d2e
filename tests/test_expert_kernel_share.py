"""``decode_experts_kernel_share`` (PR 54): the benchmark's reader of the
decode steps whose held experts ran as the kernel, over hand-made windows
of ``stats()["moe"]``."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.spec import Layout  # noqa: E402


def _layer(steps, kernel_steps=None):
    rec = {"held": [0, 16], "steps": steps}
    if kernel_steps is not None:
        rec["kernel_steps"] = kernel_steps
    return rec


@pytest.mark.parametrize("before, after, want", [
    # two counted layers: 90 + 60 of 100 + 100 steps chose the kernel
    ([(10, 10), (10, 4)], [(110, 100), (110, 64)], 75.0),
    # a program that is the kernel by its shapes, and a dense one
    ([(5, 5)], [(45, 45)], 100.0),
    ([(5, 0)], [(45, 0)], 0.0),
    # no step in the window; a program without the counter (the parent)
    ([(5, 5)], [(5, 5)], None),
    ([(5, None)], [(45, None)], None),
], ids=["counted", "kernel", "dense", "no_steps", "no_counter"])
def test_the_share_is_the_windows_delta_over_the_expert_layers(
        before, after, want):
    reader = Layout().reader("decode_experts_kernel_share")
    facts = {f"stats{i}": {"moe": {f"block{j}_experts": _layer(*rec)
                                   for j, rec in enumerate(side)}}
             for i, side in enumerate((before, after))}
    assert reader.read({"facts": facts}) == want
    assert reader.read({"facts": {}}) is None


def test_the_entry_lists_the_routed_cells():
    """PR 54's four, since PR 55 the MiMo cell, since PR 58 the Ling cell
    and since PR 63 the GLM cell, appended."""
    (entry,) = [m for m in Layout().bench["per_layer"]
                if m["name"] == "decode_experts_kernel_share"]
    assert entry == {
        "name": "decode_experts_kernel_share", "unit": "%",
        "better": "higher", "source": "program_counter",
        "layer": "Expert layer", "moves": "serve_tokens_per_s",
        "workloads": ["axk1-ep16.serve-reasoning",
                      "nemotron3-super-ep4.serve-agents",
                      "trinity-large-ep8.serve-mixedlengths",
                      "zaya1-8b-pp2.serve-chains",
                      "mimo-v2.5-ep16.serve-codebases",
                      "ling-3.0-flash-ep8.serve-longanswers",
                      "glm-5.3-flash-ep8.serve-repositories"]}
