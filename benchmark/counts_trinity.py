"""What a model of windowed and full attention layers with gated grouped
heads and routed experts needs, counted from the configuration's shapes:
its parameters, what a request keeps, the bytes a decode step has to move
and the operations a prefill chunk has to do. The roofline shares divide
these by measured device time; they live here, with the benchmark, read
the same work whatever implements it, and are counted LOW (the embedding
looked up and not read, gains and biases left out of a step's bytes, only
the experts that got a row, a windowed layer's rows at ``min(length,
window)``, only the keys a query sees, only the pairs the routing named
among the held experts, the head for no token of a chunk) so that no
share can pass 100 %.

A configuration may be one holder's share and one stage of a pipeline
(``reference/trinity.py``, "The share"): ``num_experts`` is the experts
held, ``published.num_experts`` the router's width, and ``layer_types``
the stage's own layers.

This PR writes no kernel: a windowed layer's decode step reads its ring
through the paged decode kernel the benchmark has, a chunk's attention is
a walk over key spans in XLA, the experts' products are PR 41's kernel.
"""

from __future__ import annotations

from typing import Dict

SLIDING, FULL = "sliding_attention", "full_attention"


def _z(config: Dict) -> Dict:
    pub = config.get("published") or {}
    held = int(config["num_experts"])
    types = list(config["layer_types"])
    dense = int(config["num_dense_layers"])
    return dict(
        v=int(config["vocab_size"]), e=int(config["hidden_size"]),
        layers=len(types), windowed=types.count(SLIDING),
        full=types.count(FULL), dense=dense, x=len(types) - dense,
        heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]),
        d=int(config["head_dim"]), window=int(config["sliding_window"]),
        wd=int(config["intermediate_size"]),
        we=int(config["moe_intermediate_size"]),
        shared=int(config.get("num_shared_experts", 1)), held=held,
        routed=int(pub.get("num_experts", held)),
        k=int(config["num_experts_per_tok"]))


def attention_matrix_params(config: Dict) -> int:
    """wq, the gate and wo over every query head, wk and wv over the
    key-value heads."""
    z = _z(config)
    return z["e"] * z["d"] * (3 * z["heads"] + 2 * z["kv_heads"])


def expert_params(config: Dict) -> int:
    """One gated expert's three matrices (the shared expert's too)."""
    z = _z(config)
    return 3 * z["e"] * z["we"]


def expert_layer_fixed_params(config: Dict) -> int:
    """What an expert layer holds whatever the routing: the router and
    the shared expert."""
    z = _z(config)
    return z["e"] * z["routed"] + z["shared"] * expert_params(config)


def layer_fixed_params(config: Dict) -> int:
    """The matrices every token passes, all layers, the head left out:
    attention, the dense layers' MLPs, the routers and shared experts."""
    z = _z(config)
    return (z["layers"] * attention_matrix_params(config)
            + z["dense"] * 3 * z["e"] * z["wd"]
            + z["x"] * expert_layer_fixed_params(config))


def matrix_params(config: Dict, expert_hit_share: float = 1.0) -> float:
    """Parameters that sit in a matrix product of one decode step: every
    projection of every layer, the routers, the shared experts, the head,
    and of the held routed experts the share that got a row. The
    embedding is looked up row by row and is not among them."""
    z = _z(config)
    return (layer_fixed_params(config)
            + z["x"] * z["held"] * expert_params(config) * expert_hit_share
            + z["e"] * z["v"])


def param_count(config: Dict) -> int:
    """Every parameter the holder keeps: the matrices, the embedding, the
    selection biases and the norm gains (four a layer, q's and k's, the
    final one)."""
    z = _z(config)
    gains = z["layers"] * (4 * z["e"] + 2 * z["d"]) + z["e"]
    return (int(matrix_params(config)) + z["v"] * z["e"] + gains
            + z["x"] * z["routed"])


def kv_row_bytes(config: Dict, kv_bytes: int = 2) -> int:
    """Keys and values of one token in ONE layer."""
    z = _z(config)
    return 2 * z["kv_heads"] * z["d"] * kv_bytes


def request_bytes(config: Dict, length: int, kv_bytes: int = 2) -> int:
    """What a request of ``length`` tokens needs over all layers: the full
    layers every token, the windowed layers ``min(length, window)``."""
    z = _z(config)
    return kv_row_bytes(config, kv_bytes) * (
        z["full"] * length + z["windowed"] * min(length, z["window"]))


def ring_bytes(config: Dict, kv_bytes: int = 2) -> int:
    """One request's ring in ONE windowed layer: ``window`` rows."""
    return _z(config)["window"] * kv_row_bytes(config, kv_bytes)


def window_attend_bytes(config: Dict, rows: float, kv_bytes: int = 2
                        ) -> float:
    """Bytes the windowed layers' decode attend has to read for ``rows``
    visible rows a layer (the sum over the active slots of ``min(length +
    1, window)``): each row's keys and values once, in every windowed
    layer."""
    return _z(config)["windowed"] * rows * kv_row_bytes(config, kv_bytes)


def decode_bytes_per_step(config: Dict, window_rows: float, full_rows: float,
                          expert_hit_share: float = 1.0,
                          weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one decode step has to move at the least: each matrix once,
    each visible row's keys and values once. ``window_rows``: the sum over
    the active slots of ``min(length + 1, window)``; ``full_rows``: of
    ``length + 1``."""
    z = _z(config)
    return (matrix_params(config, expert_hit_share) * weight_bytes
            + window_attend_bytes(config, window_rows, kv_bytes)
            + z["full"] * full_rows * kv_row_bytes(config, kv_bytes))


def chunk_flops(config: Dict, tokens: float, pairs_held: float,
                keys_full: float, keys_window: float) -> float:
    """Operations a prefill chunk of ``tokens`` real tokens needs: every
    fixed matrix once a token, the held experts' matrices once a pair the
    routing named among them (``pairs_held``, all expert layers), and the
    scores and the weighted sum of each (query, visible key) pair
    (``keys_full``: the sum over the chunk's tokens of ``position + 1``,
    one full layer's; ``keys_window``: of ``min(position + 1, window)``).
    The head runs for one row of a prompt's last chunk: left out."""
    z = _z(config)
    return (2.0 * tokens * layer_fixed_params(config)
            + 2.0 * pairs_held * expert_params(config)
            + 4.0 * z["heads"] * z["d"] * (z["full"] * keys_full
                                           + z["windowed"] * keys_window))
