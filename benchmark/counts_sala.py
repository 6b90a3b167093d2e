"""What a model of block-sparse attention layers among decay-only linear
attention layers (MiniCPM-SALA) needs, counted from the configuration's
shapes: its parameters, the bytes a decode step has to move, the
operations of a prefill chunk. The roofline and peak shares divide these
by measured device time; they live here, with the benchmark, and are
counted LOW (the embedding looked up and not read, gains left out, the
states at their float32 bytes once in and once out, only the blocks the
selection's counter proves, a chunk's attention over no more context than
the counters prove) so that no share can pass 100 %.

A configuration may be one stage of a pipeline: ``mixer_types`` is then
the stage's own list.
"""

from __future__ import annotations

from typing import Dict

SPARSE, LINEAR = "minicpm4", "lightning-attn"


def _z(config: Dict) -> Dict[str, int]:
    types = list(config["mixer_types"])
    sp = config["sparse_config"]
    return dict(
        v=int(config["vocab_size"]), e=int(config["hidden_size"]),
        w=int(config["intermediate_size"]),
        sparse=types.count(SPARSE), linear=types.count(LINEAR),
        h=int(config["num_attention_heads"]),
        kv=int(config["num_key_value_heads"]), d=int(config["head_dim"]),
        lh=int(config["lightning_nh"]), ld=int(config["lightning_head_dim"]),
        block=int(sp["block_size"]), stride=int(sp["kernel_stride"]),
        topk=int(sp["topk"]))


def sparse_layer_matrix_params(config: Dict) -> int:
    """W_q, W_g, W_o over the query heads, W_k, W_v over the key-value
    heads, and the MLP's three."""
    z = _z(config)
    return (z["e"] * z["d"] * (3 * z["h"] + 2 * z["kv"])
            + 3 * z["e"] * z["w"])


def linear_layer_matrix_params(config: Dict) -> int:
    """W_q, W_k, W_v, W_g, W_o and the MLP's three."""
    z = _z(config)
    return 5 * z["e"] * z["lh"] * z["ld"] + 3 * z["e"] * z["w"]


def layer_matrix_params(config: Dict) -> int:
    z = _z(config)
    return (z["sparse"] * sparse_layer_matrix_params(config)
            + z["linear"] * linear_layer_matrix_params(config))


def matrix_params(config: Dict) -> int:
    """Parameters that sit in a matrix product of one decode step: every
    projection of every layer, the MLPs and the head. The embedding is
    looked up row by row and is not among them."""
    z = _z(config)
    return layer_matrix_params(config) + z["e"] * z["v"]


def param_count(config: Dict) -> int:
    """Every parameter the stage keeps: the matrices, the embedding and
    the norm gains (two a block and the final one of width E, two or
    three a mixer of a head's width)."""
    z = _z(config)
    gains = ((z["sparse"] + z["linear"]) * 2 * z["e"] + z["e"]
             + z["sparse"] * 2 * z["d"] + z["linear"] * 3 * z["ld"])
    return matrix_params(config) + z["v"] * z["e"] + gains


def state_bytes(config: Dict) -> int:
    """One request's float32 state in ONE linear layer."""
    z = _z(config)
    return z["lh"] * z["ld"] * z["ld"] * 4


def request_bytes(config: Dict) -> int:
    """What a request keeps over all linear layers."""
    return _z(config)["linear"] * state_bytes(config)


def kv_bytes_per_token(config: Dict, kv_bytes: int = 2) -> int:
    """Keys and values of one token over all sparse layers."""
    z = _z(config)
    return z["sparse"] * 2 * z["kv"] * z["d"] * kv_bytes


def kernel_bytes_per_token(config: Dict, kv_bytes: int = 2) -> float:
    """Pooled keys (one every ``stride`` tokens) over all sparse layers,
    a token."""
    z = _z(config)
    return z["sparse"] * z["kv"] * z["d"] * kv_bytes / z["stride"]


def block_bytes(config: Dict, kv_bytes: int = 2) -> int:
    """Keys and values of one block over the key-value heads of ONE
    sparse layer (a slot's step reads ``topk`` blocks a head, each head
    its own picks)."""
    z = _z(config)
    return z["block"] * 2 * z["kv"] * z["d"] * kv_bytes


def decode_bytes_per_step(config: Dict, state_rows: float,
                          blocks_selected: float, live_tokens: float,
                          weight_bytes: int = 2) -> float:
    """Bytes one decode step has to move at the least: each matrix once,
    each stepped state once in and once out, the selected blocks' keys and
    values of every sparse layer once, every live request's pooled keys
    once. ``state_rows``: active slots x linear layers;
    ``blocks_selected``: the blocks the active slots' steps read, one
    layer's count (the counter's); ``live_tokens``: the sum over the
    active slots of the tokens cached."""
    z = _z(config)
    return (matrix_params(config) * weight_bytes
            + state_rows * 2 * state_bytes(config)
            + blocks_selected * z["sparse"] * block_bytes(config)
            + live_tokens * kernel_bytes_per_token(config))


def chunk_flops(config: Dict, tokens: float, context: float = 0.0) -> float:
    """Operations a prefill chunk of ``tokens`` live tokens needs at the
    least: every layer's matrices once a token (the head is computed for
    one row and left out), the linear layers' state products (``q S`` and
    ``k^T v``: 4 D^2 a head and token), and the sparse layers' attention
    over ``min(context, (topk - 1) blocks)`` keys a query (scores and
    weighted sum: 4 D a head and key), ``context`` the tokens before the
    chunk that the counters prove (0: none)."""
    z = _z(config)
    keys = min(float(context), (z["topk"] - 1) * z["block"])
    return tokens * (2.0 * layer_matrix_params(config)
                     + z["linear"] * 4.0 * z["lh"] * z["ld"] * z["ld"]
                     + z["sparse"] * 4.0 * z["h"] * z["d"] * keys)
