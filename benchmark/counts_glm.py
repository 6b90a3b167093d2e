"""What a model of Kimi-Delta-Attention layers beside ONE sparse latent
layer in four (a learned indexer over pooled keys), four residual streams
and routed experts needs, counted from the configuration's shapes: its
parameters by part, what a request and a token keep, the memory of a pool
of slots, the bytes a decode step has to move by part, the operations of a
prompt's chunk by part, and the bytes the sparse layer's step has to read.
The roofline shares divide these by measured device time; they live here,
with the benchmark, and are counted LOW (the fewest live tokens the
counters prove, the embedding looked up and not read, gains, taps, tails
and the stream mixes' small products left out, only the experts that got a
row, a chunk's attention over the rows TAKEN, not the rows walked) so that
no share can pass 100 %.

A configuration may be one holder's share (``reference/glm.py``, "The
share"): ``n_routed_experts`` is then the experts held,
``published.n_routed_experts`` the router's width.
"""

from __future__ import annotations

from typing import Dict

SPARSE = "deepseek_sparse_attention"


def _z(config: Dict) -> Dict[str, int]:
    pub = config.get("published") or {}
    lin = config["linear_attn_config"]
    held = int(config["n_routed_experts"])
    types, mlps = config["layer_types"], config["mlp_layer_types"]
    sparse = sum(t == SPARSE for t in types)
    dense = sum(m == "dense" for m in mlps)
    return dict(
        v=int(config["vocab_size"]), e=int(config["hidden_size"]),
        n=int(config["hc_mult"]), layers=len(types), sparse=sparse,
        kda=len(types) - sparse, dense=dense, moe=len(types) - dense,
        kh=int(lin["num_heads"]), d=int(lin["head_dim"]),
        taps=int(lin["short_conv_kernel_size"]),
        h=int(config["num_attention_heads"]),
        qr=int(config["q_lora_rank"]), kr=int(config["kv_lora_rank"]),
        dn=int(config["qk_nope_head_dim"]), dv=int(config["v_head_dim"]),
        ih=int(config["index_n_heads"]), idim=int(config["index_head_dim"]),
        pool=int(config["index_kpool"]), topk=int(config["index_topk"]),
        wd=int(config["intermediate_size"]),
        we=int(config["moe_intermediate_size"]),
        ws=(int(config.get("n_shared_experts", 0))
            * int(config["moe_intermediate_size"])),
        k=int(config["num_experts_per_tok"]),
        held=held, routed=int(pub.get("n_routed_experts", held)))


def kda_matrix_params(config: Dict) -> int:
    """W_q, W_k, W_v, W_o, the decay's and the gate's two ranks, beta's
    columns a head."""
    z = _z(config)
    wide = z["kh"] * z["d"]
    return 4 * z["e"] * wide + 2 * z["d"] * (z["e"] + wide) + z["e"] * z["kh"]


def indexer_params(config: Dict) -> int:
    """W_qI, W_kI and W_w."""
    z = _z(config)
    return z["qr"] * z["ih"] * z["idim"] + z["e"] * (z["idim"] + z["ih"])


def sparse_matrix_params(config: Dict) -> int:
    """W_qa, W_qb, W_kva, W_kvb, W_o and the indexer's three."""
    z = _z(config)
    return (z["e"] * z["qr"] + z["qr"] * z["h"] * z["dn"] + z["e"] * z["kr"]
            + z["kr"] * z["h"] * (z["dn"] + z["dv"])
            + z["h"] * z["dv"] * z["e"] + indexer_params(config))


def expert_params(config: Dict) -> int:
    """One routed expert's three matrices."""
    z = _z(config)
    return 3 * z["e"] * z["we"]


def stream_mix_params(config: Dict) -> int:
    """ONE stream mix: W_hc, its three scales and its bias."""
    z = _z(config)
    coefs = 2 * z["n"] + z["n"] * z["n"]
    return z["n"] * z["e"] * coefs + 3 + coefs


def parts(config: Dict, expert_hit_share: float = 1.0) -> Dict[str, float]:
    """Parameters that sit in a matrix product of one decode step, by
    part. The embedding is looked up row by row and is not among them."""
    z = _z(config)
    return {
        "kda": z["kda"] * kda_matrix_params(config),
        "sparse_latent": z["sparse"] * sparse_matrix_params(config),
        "dense_mlp": z["dense"] * 3 * z["e"] * z["wd"],
        "router_shared": z["moe"] * (z["e"] * z["routed"]
                                     + 3 * z["e"] * z["ws"]),
        "experts": (z["moe"] * z["held"] * expert_params(config)
                    * expert_hit_share),
        "stream_mix": 2 * z["layers"] * stream_mix_params(config),
        "head": z["e"] * z["v"]}


def matrix_params(config: Dict, expert_hit_share: float = 1.0) -> float:
    return sum(parts(config, expert_hit_share).values())


def param_count(config: Dict) -> int:
    """Every parameter the holder keeps: the matrices, the embedding, the
    selection biases, the convolutions' taps, the gates' vectors, the
    index key's gain and bias and the norm gains."""
    z = _z(config)
    channels = 3 * z["kh"] * z["d"]
    small_kda = z["taps"] * channels + z["kh"] + z["kh"] * z["d"] + z["d"]
    small_sparse = z["qr"] + z["kr"] + 2 * z["idim"]
    gains = z["layers"] * 2 * z["e"] + z["e"]
    return int(matrix_params(config) + z["v"] * z["e"] + gains
               + z["kda"] * small_kda + z["sparse"] * small_sparse
               + z["moe"] * z["routed"])


def state_bytes(config: Dict) -> int:
    """One request's float32 state in ONE KDA layer, unpadded."""
    z = _z(config)
    return z["kh"] * z["d"] * z["d"] * 4


def request_bytes(config: Dict, tail_bytes: int = 2) -> int:
    """What a request keeps: over the KDA layers the states and the
    convolutions' tails, over the sparse layers the open pool's float32
    sum."""
    z = _z(config)
    return (z["kda"] * (state_bytes(config)
                        + (z["taps"] - 1) * 3 * z["kh"] * z["d"] * tail_bytes)
            + z["sparse"] * z["idim"] * 4)


def row_bytes(config: Dict, kv_bytes: int = 2) -> int:
    """A token's latent row in ONE sparse layer."""
    return _z(config)["kr"] * kv_bytes


def pool_key_bytes(config: Dict, kv_bytes: int = 2) -> int:
    """A pool's index key in ONE sparse layer."""
    return _z(config)["idim"] * kv_bytes


def kv_bytes_per_token(config: Dict, kv_bytes: int = 2) -> float:
    """Cache bytes of one token over all sparse layers: its row and its
    share of its pool's key."""
    z = _z(config)
    return z["sparse"] * (row_bytes(config, kv_bytes)
                          + pool_key_bytes(config, kv_bytes) / z["pool"])


def memory(config: Dict, slots: int, max_length: int,
           weight_bytes: int = 2) -> Dict[str, float]:
    """The device's memory with ``slots`` requests of at most
    ``max_length`` tokens reserved, by part, in bytes."""
    out = {"weights": param_count(config) * weight_bytes,
           "rows_and_keys": slots * max_length * kv_bytes_per_token(config),
           "states": (slots + 1) * request_bytes(config)}
    out["total"] = sum(out.values())
    return out


def rows_taken(config: Dict, length: float) -> float:
    """Rows the sparse layer's query behind ``length`` cached tokens reads
    at the most: its budget, or all there are."""
    return min(length + 1, _z(config)["topk"])


def sparse_step_bytes(config: Dict, rows_live: float, rows_read: float,
                      kv_bytes: int = 2) -> float:
    """Bytes ONE sparse layer's decode step has to read of its caches: the
    pooled keys of the live rows once and the taken rows once
    (``rows_live``, ``rows_read``: sums over the step's active slots)."""
    z = _z(config)
    return (rows_live / z["pool"] * pool_key_bytes(config, kv_bytes)
            + rows_read * row_bytes(config, kv_bytes))


def decode_bytes_by_part(config: Dict, rows_live: float, rows_read: float,
                         state_rows: float, expert_hit_share: float = 1.0,
                         weight_bytes: int = 2, kv_bytes: int = 2
                         ) -> Dict[str, float]:
    """Bytes one decode step has to move at the least, by part: each
    matrix once (:func:`parts`), each stepped state once in and once out,
    the sparse layers' pooled keys and taken rows once
    (:func:`sparse_step_bytes`). ``state_rows``: active slots x KDA
    layers."""
    z = _z(config)
    out = {k: v * weight_bytes
           for k, v in parts(config, expert_hit_share).items()}
    out["states"] = state_rows * 2 * state_bytes(config)
    out["index_and_rows"] = z["sparse"] * sparse_step_bytes(
        config, rows_live, rows_read, kv_bytes)
    return out


def decode_bytes_per_step(config: Dict, rows_live: float, rows_read: float,
                          state_rows: float, expert_hit_share: float = 1.0
                          ) -> float:
    return sum(decode_bytes_by_part(config, rows_live, rows_read, state_rows,
                                    expert_hit_share).values())


def kda_step_flops_per_row(config: Dict) -> int:
    """Operations of one state's update and read-out: per number the
    decay (1), ``S^T k`` (2), ``k u^T`` (2) and ``S^T q`` (2)."""
    return state_bytes(config) // 4 * 7


def state_step_least_s(config: Dict, state_rows: float,
                       peaks: Dict[str, float]) -> float:
    """The least time the one-token state kernels of ``state_rows`` (slot,
    layer) pairs could take (``counts_ling.state_step_least_s``)."""
    return max(state_rows * 2 * state_bytes(config)
               / peaks["hbm_bytes_per_s"],
               state_rows * kda_step_flops_per_row(config)
               / peaks["bf16_flops_per_s"])


def chunk_flops_by_part(config: Dict, tokens: float, pools_scored: float,
                        rows_taken: float, pairs_held: float,
                        last_chunks: float = 0.0) -> Dict[str, float]:
    """Operations the window's chunks have to do at the least, by part, for
    ``tokens`` live tokens: every token through every matrix but the
    experts' (2 a parameter), the held experts' pairs (``pairs_held``
    over all layers), the KDA rule a token (:func:`kda_step_flops_per_row`,
    the one-token count: a chunked form does more), the indexer's scores
    (``pools_scored`` (query, pool) pairs of ONE sparse layer) and the
    attention over the rows TAKEN (``rows_taken`` (query, row) pairs of
    ONE sparse layer, keys of ``dn`` and values of ``dv`` a head); the
    head for ``last_chunks`` positions."""
    z = _z(config)
    p = parts(config)
    return {
        "matrices": 2.0 * tokens * (p["kda"] + p["sparse_latent"]
                                    + p["dense_mlp"] + p["router_shared"]
                                    + p["stream_mix"]),
        "experts": 2.0 * pairs_held * expert_params(config),
        "kda_rule": tokens * z["kda"] * kda_step_flops_per_row(config),
        "index_scores": (2.0 * z["sparse"] * pools_scored * z["ih"]
                         * z["idim"]),
        "attention": (2.0 * z["sparse"] * rows_taken * z["h"]
                      * (z["dn"] + z["dv"])),
        "head": 2.0 * last_chunks * p["head"]}


def chunk_flops(config: Dict, *args, **kw) -> float:
    return sum(chunk_flops_by_part(config, *args, **kw).values())
