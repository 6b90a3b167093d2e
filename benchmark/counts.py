"""What the work needs, counted from the configuration's shapes: the
parameters, the floating-point operations of a training step, the bytes
a decode step has to move, and the batch a chip holds. The roofline
shares divide these by measured device time; they live here, with the
benchmark, so that no later PR can count its own work.

``gpt_param_count``, ``train_bytes_estimate`` and ``train_batch_that_fits``
are copies of ``chip_smoke.py``'s helpers of the same names (PR 21),
rewritten over a configuration file's keys.
"""

from __future__ import annotations

import json
import os
from typing import Dict

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The peaks of one chip of ``device_kind``. A kind that is not in
    ``peaks.json`` is an error, not a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def _sizes(config: Dict):
    e = int(config["n_embd"])
    return (int(config["vocab_size"]), int(config["n_positions"]), e,
            int(config["n_head"]), int(config["n_layer"]),
            int(config.get("n_inner") or 4 * e))


def gpt_param_count(config: Dict) -> int:
    """Parameters of the model as ``models/gpt.py`` builds it (untied
    output head)."""
    v, p, e, _, layers, inner = _sizes(config)
    block = (4 * e * e + 4 * e            # attention q, k, v, o + biases
             + 2 * inner * e + inner + e  # the two MLP matrices + biases
             + 4 * e)                     # two LayerNorms
    return v * e + p * e + layers * block + 2 * e + e * v


def train_flops_per_token(config: Dict, seq: int) -> float:
    """Floating-point operations one token of a training step needs,
    forward and backward, nothing recomputed: 6 per parameter that sits
    in a matrix product (2 forward, 4 backward; the embedding tables are
    looked up, not multiplied) and, for causal attention, 6 per layer,
    head dimension and key position a query really attends to (QK^T and
    PV, on average (seq + 1) / 2 positions)."""
    v, _, e, _, layers, inner = _sizes(config)
    matmul_params = layers * (4 * e * e + 2 * inner * e) + e * v
    attention = layers * 2 * e * (seq + 1) / 2  # MACs per token, QK^T + PV
    return 6.0 * matmul_params + 6.0 * attention


def decode_bytes_per_step(config: Dict, live_kv_tokens: float,
                          weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one decode step has to read at the least: every weight of a
    matrix product once (the embedding tables are looked up row by row)
    and the keys and values of the tokens that are live in the active
    slots once. ``live_kv_tokens`` is the sum over the active slots of
    the tokens each has cached."""
    v, _, e, _, layers, inner = _sizes(config)
    matmul_params = layers * (4 * e * e + 2 * inner * e) + e * v
    kv = live_kv_tokens * layers * 2 * e * kv_bytes
    return matmul_params * weight_bytes + kv


def kv_bytes_per_token(config: Dict, kv_bytes: int = 2) -> int:
    _, _, e, _, layers, _ = _sizes(config)
    return layers * 2 * e * kv_bytes


def train_bytes_estimate(config: Dict, seq: int, batch: int) -> int:
    """Estimated peak footprint of one device training ``batch``
    sequences (chip_smoke.py's estimate, fitted there to the v5e: 7.9
    and 11.0 GB against 8.3 and 11.3 GB measured for GPT-2 medium at
    batch 2 and 4): 12 bytes a parameter of state, and per sample the
    bfloat16 attention probabilities, the float32 logits three times
    over, and two bfloat16 activations a block."""
    v, _, e, heads, layers, _ = _sizes(config)
    per_sample = (layers * heads * seq ** 2 * 2 + 3 * seq * v * 4
                  + layers * 2 * seq * e * 2)
    return 12 * gpt_param_count(config) + batch * per_sample


def train_batch_that_fits(config: Dict, seq: int, hbm_bytes: int) -> int:
    """Largest power-of-two batch whose estimate fits nine tenths of
    ``hbm_bytes``."""
    if train_bytes_estimate(config, seq, 1) > 0.9 * hbm_bytes:
        raise ValueError(
            f"one sample does not fit: an estimated "
            f"{train_bytes_estimate(config, seq, 1) / 1e9:.2f} GB > 0.9 * "
            f"{hbm_bytes / 1e9:.2f} GB")
    batch = 1
    while train_bytes_estimate(config, seq, 2 * batch) <= 0.9 * hbm_bytes:
        batch *= 2
    return batch
