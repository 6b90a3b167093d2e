"""What a model of Kimi-Delta-Attention layers beside latent-attention
layers, with routed experts, needs, counted from the configuration's
shapes: its parameters by part, what a request keeps, the bytes a decode
step has to move by part, the bytes and operations of the one-token state
kernel. The roofline shares divide these by measured device time; they
live here, with the benchmark, and are counted LOW (unpadded latent rows,
the fewest live tokens the counters prove, the embedding looked up and
not read, gains, taps and tails left out, only the experts that got a
row) so that no share can pass 100 %.

A configuration may be one holder's share (``reference/ling.py``, "The
share"): ``num_experts`` is then the experts held, ``published.num_experts``
the router's width, ``first_layer`` / ``num_hidden_layers`` the run of
published layers held.
"""

from __future__ import annotations

from typing import Dict


def _z(config: Dict) -> Dict[str, int]:
    pub = config.get("published") or {}
    held = int(config["num_experts"])
    first = int(config.get("first_layer", 0))
    layers = range(first, first + int(config["num_hidden_layers"]))
    period = int(config["layer_group_size"])
    latent = sum((p + 1) % period == 0 for p in layers)
    dense = sum(p < int(config["first_k_dense_replace"]) for p in layers)
    return dict(
        v=int(config["vocab_size"]), e=int(config["hidden_size"]),
        layers=len(layers), latent=latent, kda=len(layers) - latent,
        dense=dense, moe=len(layers) - dense,
        h=int(config["num_attention_heads"]), d=int(config["head_dim"]),
        taps=int(config["short_conv_kernel_size"]),
        kr=int(config["kv_lora_rank"]), dn=int(config["qk_nope_head_dim"]),
        dr=int(config["qk_rope_head_dim"]), dv=int(config["v_head_dim"]),
        wd=int(config["intermediate_size"]),
        we=int(config["moe_intermediate_size"]),
        ws=(int(config.get("num_shared_experts", 0))
            * int(config["moe_shared_expert_intermediate_size"])),
        held=held, routed=int(pub.get("num_experts", held)))


def kda_matrix_params(config: Dict) -> int:
    """W_q, W_k, W_v, the decay's W_f, W_o, and beta's and the gate's
    columns a head."""
    z = _z(config)
    return z["e"] * (5 * z["h"] * z["d"] + 2 * z["h"])


def latent_matrix_params(config: Dict) -> int:
    """W_q direct, W_kv_a, W_kv_b, the gate's columns a head, W_o."""
    z = _z(config)
    return (z["e"] * z["h"] * (z["dn"] + z["dr"]) + z["e"] * (z["kr"] + z["dr"])
            + z["kr"] * z["h"] * (z["dn"] + z["dv"]) + z["e"] * z["h"]
            + z["h"] * z["dv"] * z["e"])


def expert_params(config: Dict) -> int:
    """One routed expert's three matrices."""
    z = _z(config)
    return 3 * z["e"] * z["we"]


def parts(config: Dict, expert_hit_share: float = 1.0) -> Dict[str, float]:
    """Parameters that sit in a matrix product of one decode step, by
    part: the mixers, the dense MLPs, the routers with the shared experts,
    of the held routed experts the share that got a row, the head. The
    embedding is looked up row by row and is not among them."""
    z = _z(config)
    return {
        "kda": z["kda"] * kda_matrix_params(config),
        "latent": z["latent"] * latent_matrix_params(config),
        "dense_mlp": z["dense"] * 3 * z["e"] * z["wd"],
        "router_shared": z["moe"] * (z["e"] * z["routed"]
                                     + 3 * z["e"] * z["ws"]),
        "experts": (z["moe"] * z["held"] * expert_params(config)
                    * expert_hit_share),
        "head": z["e"] * z["v"]}


def matrix_params(config: Dict, expert_hit_share: float = 1.0) -> float:
    return sum(parts(config, expert_hit_share).values())


def param_count(config: Dict) -> int:
    """Every parameter the holder keeps: the matrices, the embedding, the
    selection biases, the convolutions' taps, the gates' vectors and the
    norm gains."""
    z = _z(config)
    channels = 3 * z["h"] * z["d"]
    small_kda = (z["taps"] * channels + z["h"] + z["h"] * z["d"] + z["d"])
    gains = z["layers"] * 2 * z["e"] + z["e"]
    return int(matrix_params(config) + z["v"] * z["e"] + gains
               + z["kda"] * small_kda + z["latent"] * z["kr"]
               + z["moe"] * z["routed"])


def state_bytes(config: Dict) -> int:
    """One request's float32 state in ONE KDA layer, unpadded."""
    z = _z(config)
    return z["h"] * z["d"] * z["d"] * 4


def request_bytes(config: Dict, tail_bytes: int = 2) -> int:
    """What a request keeps over all KDA layers: the states and the
    convolutions' tails (``taps - 1`` positions of q's, k's and v's
    channels)."""
    z = _z(config)
    return z["kda"] * (state_bytes(config)
                       + (z["taps"] - 1) * 3 * z["h"] * z["d"] * tail_bytes)


def row_width(config: Dict) -> int:
    """Numbers a token's cached row holds in a latent layer: ``[c |
    k_rope]``."""
    z = _z(config)
    return z["kr"] + z["dr"]


def kv_bytes_per_token(config: Dict, kv_bytes: int = 2) -> int:
    """Cache bytes of one token over all latent layers, unpadded."""
    return _z(config)["latent"] * row_width(config) * kv_bytes


def decode_bytes_by_part(config: Dict, live_tokens: float, state_rows: float,
                         expert_hit_share: float = 1.0,
                         weight_bytes: int = 2, kv_bytes: int = 2
                         ) -> Dict[str, float]:
    """Bytes one decode step has to move at the least, by part: each
    matrix once (:func:`parts`), each stepped state once in and once out,
    each live token's latent row once in every latent layer.
    ``state_rows``: active slots x KDA layers; ``live_tokens``: the sum
    over the active slots of the tokens cached."""
    out = {k: v * weight_bytes
           for k, v in parts(config, expert_hit_share).items()}
    out["states"] = state_rows * 2 * state_bytes(config)
    out["latent_rows"] = live_tokens * kv_bytes_per_token(config, kv_bytes)
    return out


def decode_bytes_per_step(config: Dict, live_tokens: float,
                          state_rows: float, expert_hit_share: float = 1.0,
                          weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    return sum(decode_bytes_by_part(config, live_tokens, state_rows,
                                    expert_hit_share, weight_bytes,
                                    kv_bytes).values())


def kda_step_flops_per_row(config: Dict) -> int:
    """Operations of one state's update and read-out: per number the
    decay (1), ``S^T k`` (2), ``k u^T`` (2) and ``S^T q`` (2)."""
    return state_bytes(config) // 4 * 7


def state_step_least_s(config: Dict, state_rows: float,
                       peaks: Dict[str, float]) -> float:
    """The least time the one-token state kernels of ``state_rows`` (slot,
    layer) pairs could take: the states' bytes in and out over the HBM
    peak, or their operations over the chip's peak, whichever is larger
    (the bytes, by two orders; the ``(d_k,)`` decays, keys and queries a
    head are a hundredth of a state and left out)."""
    return max(state_rows * 2 * state_bytes(config)
               / peaks["hbm_bytes_per_s"],
               state_rows * kda_step_flops_per_row(config)
               / peaks["bf16_flops_per_s"])

