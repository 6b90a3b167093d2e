"""What a MiMo-V2 share needs, counted from the configuration's shapes:
its parameters, what a request keeps, the bytes a decode step has to move
and the operations a prefill chunk has to do. The roofline shares divide
these by measured device time; they live here, with the benchmark, read
the same work whatever implements it, and are counted LOW (the embedding
looked up and not read, gains, biases and sinks left out of a step's
bytes, only the experts that got a row, a windowed layer's rows at
``min(length + 1, window)``, a full layer's to each slot's length with no
block padding, only the keys a query sees, only the pairs the routing
named among the held experts, the head for no token of a chunk) so that
no share can pass 100 %.

What differs from ``counts_trinity.py``: a key head of ``head_dim``
beside a value head of ``v_head_dim`` (192 and 128: a row is ``Hkv * (D +
Dv)`` numbers, a (query, key) pair ``2 H (D + Dv)`` operations), key-value
heads a kind of layer (4 full, 8 windowed), no gate, no shared expert,
two norms a layer.

A configuration may be one holder's share and one stage of a pipeline
(``reference/mimo.py``, "The share").
"""

from __future__ import annotations

from typing import Dict

FULL, WINDOWED = 0, 1


def _z(config: Dict) -> Dict:
    pub = config.get("published") or {}
    held = int(config["n_routed_experts"])
    kinds = [int(k) for k in config["hybrid_layer_pattern"]]
    ffn = [int(k) for k in config["moe_layer_freq"]]
    return dict(
        v=int(config["vocab_size"]), e=int(config["hidden_size"]),
        layers=len(kinds), windowed=kinds.count(WINDOWED),
        full=kinds.count(FULL), dense=ffn.count(0), x=ffn.count(1),
        heads=int(config["num_attention_heads"]),
        kv={FULL: int(config["num_key_value_heads"]),
            WINDOWED: int(config["swa_num_key_value_heads"])},
        d=int(config["head_dim"]), dv=int(config["v_head_dim"]),
        window=int(config["sliding_window"]),
        sinks=(kinds.count(WINDOWED)
               * bool(config.get("add_swa_attention_sink_bias"))
               + kinds.count(FULL)
               * bool(config.get("add_full_attention_sink_bias"))),
        wd=int(config["intermediate_size"]),
        we=int(config["moe_intermediate_size"]), held=held,
        routed=int(pub.get("n_routed_experts", held)),
        k=int(config["num_experts_per_tok"]))


def attention_matrix_params(config: Dict, kind: int) -> int:
    """One layer of ``kind``: wq over every query head's key width, wo
    over its value width, wk and wv over the kind's key-value heads."""
    z = _z(config)
    return z["e"] * (z["heads"] + z["kv"][kind]) * (z["d"] + z["dv"])


def expert_params(config: Dict) -> int:
    """One gated expert's three matrices."""
    z = _z(config)
    return 3 * z["e"] * z["we"]


def layer_fixed_params(config: Dict) -> int:
    """The matrices every token passes, all layers, the head left out:
    attention, the dense layers' MLPs, the routers."""
    z = _z(config)
    return (z["full"] * attention_matrix_params(config, FULL)
            + z["windowed"] * attention_matrix_params(config, WINDOWED)
            + z["dense"] * 3 * z["e"] * z["wd"]
            + z["x"] * z["e"] * z["routed"])


def matrix_params(config: Dict, expert_hit_share: float = 1.0) -> float:
    """Parameters that sit in a matrix product of one decode step: every
    projection of every layer, the routers, the head, and of the held
    routed experts the share that got a row. The embedding is looked up
    row by row and is not among them."""
    z = _z(config)
    return (layer_fixed_params(config)
            + z["x"] * z["held"] * expert_params(config) * expert_hit_share
            + z["e"] * z["v"])


def param_count(config: Dict) -> int:
    """Every parameter the holder keeps: the matrices, the embedding, the
    selection biases, the sinks and the norm gains (two a layer and the
    final one)."""
    z = _z(config)
    gains = z["layers"] * 2 * z["e"] + z["e"]
    return (int(matrix_params(config)) + z["v"] * z["e"] + gains
            + z["x"] * z["routed"] + z["sinks"] * z["heads"])


def kv_row_bytes(config: Dict, kind: int, kv_bytes: int = 2) -> int:
    """Keys and values of one token in ONE layer of ``kind``."""
    z = _z(config)
    return z["kv"][kind] * (z["d"] + z["dv"]) * kv_bytes


def token_bytes(config: Dict, kv_bytes: int = 2) -> int:
    """What one token takes in the paged pool: its row in every full
    layer."""
    return _z(config)["full"] * kv_row_bytes(config, FULL, kv_bytes)


def ring_bytes(config: Dict, kv_bytes: int = 2) -> int:
    """One request's rings: ``window`` rows in every windowed layer."""
    z = _z(config)
    return z["windowed"] * z["window"] * kv_row_bytes(config, WINDOWED,
                                                      kv_bytes)


def request_bytes(config: Dict, length: int, kv_bytes: int = 2) -> int:
    """What a request of ``length`` tokens needs over all layers: the full
    layers every token, the windowed layers ``min(length, window)``."""
    z = _z(config)
    return (token_bytes(config, kv_bytes) * length
            + z["windowed"] * min(length, z["window"])
            * kv_row_bytes(config, WINDOWED, kv_bytes))


def full_attend_bytes(config: Dict, rows: float, kv_bytes: int = 2) -> float:
    """Bytes the full layers' decode attend has to read for ``rows`` live
    rows a layer (the sum over the active slots of ``length + 1``): each
    row's keys and values once, in every full layer, no block padding."""
    return _z(config)["full"] * rows * kv_row_bytes(config, FULL, kv_bytes)


def window_attend_bytes(config: Dict, rows: float, kv_bytes: int = 2
                        ) -> float:
    """The same of the windowed layers for ``rows`` visible rows a layer
    (the sum over the active slots of ``min(length + 1, window)``)."""
    return _z(config)["windowed"] * rows * kv_row_bytes(config, WINDOWED,
                                                        kv_bytes)


def decode_bytes_per_step(config: Dict, window_rows: float, full_rows: float,
                          expert_hit_share: float = 1.0,
                          weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one decode step has to move at the least: each matrix once,
    each visible row's keys and values once."""
    return (matrix_params(config, expert_hit_share) * weight_bytes
            + window_attend_bytes(config, window_rows, kv_bytes)
            + full_attend_bytes(config, full_rows, kv_bytes))


def attention_pair_flops(config: Dict) -> float:
    """Operations one (query, seen key) pair costs in one layer: every
    query head's score over the key width and its weighted sum over the
    value width."""
    z = _z(config)
    return 2.0 * z["heads"] * (z["d"] + z["dv"])


def _last_layer(config: Dict) -> Dict:
    """What a chunk that is NOT its prompt's last leaves undone: the
    program ends such a chunk behind the last layer's write of its keys
    and values (nothing reads what that layer would attend), so the last
    layer's wq and wo, its attention and its feed-forward run for a
    prompt's last chunk only. ``fixed``: those matrices' parameters;
    ``kind`` the layer's."""
    z = _z(config)
    kind = int(config["hybrid_layer_pattern"][-1])
    ffn = (z["e"] * z["routed"] if int(config["moe_layer_freq"][-1])
           else 3 * z["e"] * z["wd"])
    return {"kind": kind,
            "fixed": z["e"] * z["heads"] * (z["d"] + z["dv"]) + ffn}


def chunk_attention_flops(config: Dict, keys_full: float, keys_window: float,
                          keys_last: float = None) -> float:
    """A chunk's attention alone: ``keys_full`` (query, seen key) pairs in
    each full layer (the sum over the chunk's tokens of ``position + 1``),
    ``keys_window`` in each windowed one (of ``min(position + 1,
    window)``). The last layer attends in a prompt's last chunk only
    (:func:`_last_layer`): its pairs are ``keys_last``, the full-layer
    pairs of the chunks that were their prompt's last (a last layer that
    is windowed, or a program that does not count them: none, which is
    low)."""
    z = _z(config)
    last = _last_layer(config)["kind"]
    full, windowed = z["full"] - (last == FULL), z["windowed"] - (
        last == WINDOWED)
    return attention_pair_flops(config) * (
        full * keys_full + windowed * keys_window
        + (keys_last or 0.0) * (last == FULL))


def chunk_flops(config: Dict, tokens: float, pairs_held: float,
                keys_full: float, keys_window: float,
                tokens_last: float = 0.0, keys_last: float = None) -> float:
    """Operations a prefill chunk of ``tokens`` real tokens needs: every
    fixed matrix once a token (the last layer's behind its write once a
    token of a prompt's LAST chunk, ``tokens_last``: :func:`_last_layer`),
    the held experts' matrices once a pair the routing named among them
    (``pairs_held``, all expert layers, as the program counted them), and
    the chunk's attention. The head runs for one row of a prompt's last
    chunk: left out."""
    skipped = _last_layer(config)["fixed"]
    return (2.0 * tokens * (layer_fixed_params(config) - skipped)
            + 2.0 * tokens_last * skipped
            + 2.0 * pairs_held * expert_params(config)
            + chunk_attention_flops(config, keys_full, keys_window,
                                    keys_last))
