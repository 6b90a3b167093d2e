"""What a model of latent attention and routed experts needs, counted
from the configuration's shapes: its parameters, the bytes a decode step
has to read, the bytes and operations of the latent-attention decode
kernel. The roofline shares divide these by measured device time; they
live here, with the benchmark, and are counted LOW (unpadded rows, the
fewest live tokens the counters prove, the embedding looked up and not
read, only the experts that got a row) so that no share can pass 100 %.

A configuration may be one holder's share (``reference/axk1.py``, "The
share"): ``n_routed_experts`` is then the experts held and
``published.n_routed_experts`` the router's width.
"""

from __future__ import annotations

from typing import Dict


def _z(config: Dict) -> Dict[str, int]:
    pub = config.get("published") or {}
    held = int(config["n_routed_experts"])
    return dict(
        v=int(config["vocab_size"]), e=int(config["hidden_size"]),
        layers=int(config["num_hidden_layers"]),
        dense=int(config["first_k_dense_replace"]),
        h=int(config["num_attention_heads"]),
        qr=int(config["q_lora_rank"]), kr=int(config["kv_lora_rank"]),
        dn=int(config["qk_nope_head_dim"]), dr=int(config["qk_rope_head_dim"]),
        dv=int(config["v_head_dim"]), wd=int(config["intermediate_size"]),
        we=int(config["moe_intermediate_size"]),
        shared=int(config.get("n_shared_experts", 0)), held=held,
        routed=int(pub.get("n_routed_experts", held)))


def attention_matrix_params(config: Dict) -> int:
    z = _z(config)
    return (z["e"] * z["qr"] + z["qr"] * z["h"] * (z["dn"] + z["dr"])
            + z["e"] * (z["kr"] + z["dr"])
            + z["kr"] * z["h"] * (z["dn"] + z["dv"])
            + z["h"] * z["dv"] * z["e"])


def expert_params(config: Dict) -> int:
    """One routed expert's three matrices."""
    z = _z(config)
    return 3 * z["e"] * z["we"]


def matrix_params(config: Dict, expert_hit_share: float = 1.0) -> float:
    """Parameters that sit in a matrix product of one decode step: every
    projection of every layer, the router, the shared expert, the head,
    and of the held routed experts the share that got a row. The
    embedding is looked up row by row and is not among them."""
    z = _z(config)
    n_exp = z["layers"] - z["dense"]
    per_expert_layer = (z["e"] * z["routed"] + z["shared"] * expert_params(config)
                        + z["held"] * expert_params(config) * expert_hit_share)
    return (z["layers"] * attention_matrix_params(config)
            + z["dense"] * 3 * z["e"] * z["wd"]
            + n_exp * per_expert_layer + z["e"] * z["v"])


def param_count(config: Dict) -> int:
    """Every parameter the holder keeps: the matrices, the embedding and
    the norm gains."""
    z = _z(config)
    gains = z["layers"] * (2 * z["e"] + z["qr"] + z["kr"]) + z["e"]
    return int(matrix_params(config)) + z["v"] * z["e"] + gains


def row_width(config: Dict) -> int:
    """Numbers a token's cached row holds in each layer: ``[c | k_rope]``."""
    z = _z(config)
    return z["kr"] + z["dr"]


def latent_bytes_per_token(config: Dict, kv_bytes: int = 2) -> int:
    """Cache bytes of one token over all layers, unpadded."""
    return _z(config)["layers"] * row_width(config) * kv_bytes


def decode_bytes_per_step(config: Dict, live_tokens: float,
                          expert_hit_share: float = 1.0,
                          weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one decode step has to read at the least: each matrix once
    (``matrix_params``) and each live token's row once in every layer.
    ``live_tokens``: the sum over the active slots of the tokens cached."""
    return (matrix_params(config, expert_hit_share) * weight_bytes
            + live_tokens * latent_bytes_per_token(config, kv_bytes))


def latent_attention_flops_per_row(config: Dict) -> int:
    """Operations the decode kernel needs for one cached row of one
    layer: per head a score over the row's ``row_width`` numbers and a
    weighted sum over its ``kv_lora_rank`` latent numbers, 2 each."""
    z = _z(config)
    return z["h"] * (row_width(config) + z["kr"]) * 2


def latent_attention_least_s(config: Dict, live_tokens: float,
                             peaks: Dict[str, float],
                             kv_bytes: int = 2) -> float:
    """The least time all layers' decode kernels of one step could take:
    the larger of the rows' bytes over the HBM peak and the operations
    over the bfloat16 peak."""
    z = _z(config)
    rows = live_tokens * z["layers"]
    return max(rows * row_width(config) * kv_bytes / peaks["hbm_bytes_per_s"],
               rows * latent_attention_flops_per_row(config)
               / peaks["bf16_flops_per_s"])
