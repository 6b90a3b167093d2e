"""What a model of compressed-convolutional-attention and top-1 routed
expert sublayers needs, counted from the configuration's shapes: its
parameters, what a token and a request keep, the bytes a decode step has
to move and the operations a prefill chunk has to do. The roofline shares
divide these by measured device time; they live here, with the benchmark,
read the same work whatever implements it, and are counted LOW (the
embedding looked up and not read, vectors left out of a step's bytes,
only the experts that got a row, only the keys a query sees, only the
pairs the routing named, the head for no token of a chunk) so that no
share can pass 100 %.

A configuration may be one stage of a pipeline (``reference/zaya.py``):
``num_hidden_layers`` its own layers, from ``first_layer`` on.

This PR writes no kernel: a decode step reads the pair through the paged
decode kernel the benchmark has, a chunk's attention is
``kernels/chunk_attention.py``, the experts' products the grouped kernel.
"""

from __future__ import annotations

from typing import Dict


def _z(config: Dict) -> Dict:
    h, g = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    return dict(
        v=int(config["vocab_size"]), e=int(config["hidden_size"]),
        layers=int(config["num_hidden_layers"]),
        first=int(config.get("first_layer", 0)), heads=h, kv_heads=g,
        d=int(config["head_dim"]), c=(h + g) * int(config["head_dim"]),
        t0=int(config["cca_time0"]), t1=int(config["cca_time1"]),
        n=int(config["num_experts"]), k=int(config["num_experts_per_tok"]),
        w=int(config["moe_intermediate_size"]),
        r=int(config["router_hidden_size"]))


def attention_matrix_params(config: Dict) -> int:
    """Wq and Wo over every query head, Wk, Wv1 and Wv2 over the
    key-value heads, and the grouped convolution's matrices."""
    z = _z(config)
    return (z["e"] * z["d"] * (2 * z["heads"] + 2 * z["kv_heads"])
            + (z["heads"] + z["kv_heads"]) * z["t1"] * z["d"] * z["d"])


def router_matrix_params(config: Dict) -> int:
    z = _z(config)
    return z["e"] * z["r"] + 2 * z["r"] * z["r"] + z["r"] * z["n"]


def expert_params(config: Dict) -> int:
    """One gated expert's three matrices."""
    z = _z(config)
    return 3 * z["e"] * z["w"]


def layer_fixed_params(config: Dict) -> int:
    """The matrices every token passes, all layers, the head left out."""
    return _z(config)["layers"] * (attention_matrix_params(config)
                                   + router_matrix_params(config))


def layer_vector_params(config: Dict, i: int) -> int:
    """Layer ``i``'s vectors: two norms, eight residual scales, the
    depthwise convolution and both convolutions' biases, the
    temperatures, the router's biases, norm and depth scale (none in the
    MODEL's first layer) and the balancing bias."""
    z = _z(config)
    return (10 * z["e"] + (z["t0"] + 2) * z["c"] + z["kv_heads"]
            + (4 + (z["first"] + i > 0)) * z["r"] + z["n"])


def matrix_params(config: Dict, expert_hit_share: float = 1.0) -> float:
    """Parameters that sit in a matrix product of one decode step: every
    projection of every layer, the routers, the table once (as the head;
    the embedding is looked up row by row), and of the experts the share
    that got a row."""
    z = _z(config)
    return (layer_fixed_params(config)
            + z["layers"] * z["n"] * expert_params(config) * expert_hit_share
            + z["e"] * z["v"])


def param_count(config: Dict) -> int:
    """Every parameter the stage keeps, the table once."""
    z = _z(config)
    return (int(matrix_params(config)) + z["e"]
            + sum(layer_vector_params(config, i)
                  for i in range(z["layers"])))


def kv_row_bytes(config: Dict, kv_bytes: int = 2) -> int:
    """Keys and values of one token in ONE layer."""
    z = _z(config)
    return 2 * z["kv_heads"] * z["d"] * kv_bytes


def kv_bytes_per_token(config: Dict, kv_bytes: int = 2) -> int:
    """Keys and values of one token over all layers."""
    return _z(config)["layers"] * kv_row_bytes(config, kv_bytes)


def state_bytes(config: Dict, kv_bytes: int = 2) -> int:
    """What a request keeps beside its tokens in ONE layer: the last
    ``cca_time0 + cca_time1 - 2`` rows of z and the last token's half
    value."""
    z = _z(config)
    return ((z["t0"] + z["t1"] - 2) * z["c"]
            + z["kv_heads"] * z["d"] // 2) * kv_bytes


def request_bytes(config: Dict, length: int, kv_bytes: int = 2) -> int:
    """What a request of ``length`` tokens needs over all layers."""
    z = _z(config)
    return (length * kv_bytes_per_token(config, kv_bytes)
            + z["layers"] * state_bytes(config, kv_bytes))


def attend_bytes_per_step(config: Dict, live_tokens: float,
                          kv_bytes: int = 2) -> float:
    """Bytes the decode step's attention has to read: every live token's
    keys and values once a layer."""
    return live_tokens * kv_bytes_per_token(config, kv_bytes)


def decode_bytes_per_step(config: Dict, live_tokens: float,
                          state_rows: float, expert_hit_share: float = 1.0,
                          weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one decode step has to move at the least: each matrix once,
    each live token's keys and values once, each stepped (slot, layer)
    row's tail and half value once in and once out (``state_rows``: the
    sum over the layers of the active slots)."""
    return (matrix_params(config, expert_hit_share) * weight_bytes
            + attend_bytes_per_step(config, live_tokens, kv_bytes)
            + 2 * state_rows * state_bytes(config, kv_bytes))


def chunk_flops(config: Dict, tokens: float, pairs_held: float,
                keys: float) -> float:
    """Operations a prefill chunk of ``tokens`` real tokens needs: every
    fixed matrix once a token, an expert's matrices once a pair the
    routing named (``pairs_held``, all layers), and the scores and the
    weighted sum of each (query, visible key) pair (``keys``: the sum
    over the chunk's tokens of ``position + 1``, one layer's). The head
    runs for one row of a prompt's last chunk: left out."""
    z = _z(config)
    return (2.0 * tokens * layer_fixed_params(config)
            + 2.0 * pairs_held * expert_params(config)
            + 4.0 * z["heads"] * z["d"] * z["layers"] * keys)
