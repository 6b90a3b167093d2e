"""What a model of gated-delta-rule layers among full-attention layers
needs, counted from the configuration's shapes: its parameters, the bytes
a decode step has to move, the bytes and operations of the state-update
kernel. The roofline shares divide these by measured device time; they
live here, with the benchmark, and are counted LOW (the embedding looked
up and not read, gains and the convolution's taps and tails left out, the
states at their unpadded float32 bytes, the fewest live tokens the
counters prove) so that no share can pass 100 %.

A configuration may be one stage of a pipeline: ``layer_types`` is then
the stage's own list.
"""

from __future__ import annotations

from typing import Dict

LINEAR, FULL = "linear_attention", "full_attention"


def _z(config: Dict) -> Dict[str, int]:
    types = list(config["layer_types"])
    return dict(
        v=int(config["vocab_size"]), e=int(config["hidden_size"]),
        w=int(config["intermediate_size"]),
        linear=types.count(LINEAR), full=types.count(FULL),
        lh=int(config["linear_num_value_heads"]),
        dk=int(config["linear_key_head_dim"]),
        dv=int(config["linear_value_head_dim"]),
        taps=int(config["linear_conv_kernel_dim"]))


def linear_layer_matrix_params(config: Dict) -> int:
    """W_q, W_k, W_v, W_g, W_o, W_a, W_b and the MLP's three."""
    z = _z(config)
    h = z["lh"]
    return (z["e"] * (2 * h * z["dk"] + 3 * h * z["dv"] + 2 * h)
            + 3 * z["e"] * z["w"])


def full_layer_matrix_params(config: Dict) -> int:
    z = _z(config)
    return 4 * z["e"] * z["e"] + 3 * z["e"] * z["w"]


def matrix_params(config: Dict) -> int:
    """Parameters that sit in a matrix product of one decode step: every
    projection of every layer, the MLPs and the head. The embedding is
    looked up row by row and is not among them."""
    z = _z(config)
    return (z["linear"] * linear_layer_matrix_params(config)
            + z["full"] * full_layer_matrix_params(config)
            + z["e"] * z["v"])


def param_count(config: Dict) -> int:
    """Every parameter the stage keeps: the matrices, the embedding, the
    convolutions' taps, the gates' two vectors and the norm gains."""
    z = _z(config)
    channels = z["lh"] * (2 * z["dk"] + z["dv"])
    small_linear = z["taps"] * channels + 2 * z["lh"] + z["dv"]
    small_full = 2 * z["e"]                         # q_norm, k_norm
    gains = (z["linear"] + z["full"]) * 2 * z["e"] + z["e"]
    return (matrix_params(config) + z["v"] * z["e"] + gains
            + z["linear"] * small_linear + z["full"] * small_full)


def state_bytes(config: Dict) -> int:
    """One request's float32 state in ONE linear layer, unpadded."""
    z = _z(config)
    return z["lh"] * z["dk"] * z["dv"] * 4


def request_bytes(config: Dict, tail_bytes: int = 2) -> int:
    """What a request keeps over all linear layers: the states and the
    convolutions' tails (``taps - 1`` positions of every channel)."""
    z = _z(config)
    channels = z["lh"] * (2 * z["dk"] + z["dv"])
    return z["linear"] * (state_bytes(config)
                          + (z["taps"] - 1) * channels * tail_bytes)


def kv_bytes_per_token(config: Dict, kv_bytes: int = 2) -> int:
    """Keys and values of one token over all full layers."""
    z = _z(config)
    return z["full"] * 2 * z["e"] * kv_bytes


def decode_bytes_per_step(config: Dict, live_tokens: float,
                          state_rows: float, weight_bytes: int = 2,
                          kv_bytes: int = 2) -> float:
    """Bytes one decode step has to move at the least: each matrix once,
    each stepped state once in and once out, each live token's keys and
    values once. ``state_rows``: active slots x linear layers;
    ``live_tokens``: the sum over the active slots of the tokens cached."""
    return (matrix_params(config) * weight_bytes
            + state_rows * 2 * state_bytes(config)
            + live_tokens * kv_bytes_per_token(config, kv_bytes))


def gated_delta_flops_per_row(config: Dict) -> int:
    """Operations of one state's update and read-out: per number the
    decay (1), ``S^T k`` (2), ``k u^T`` (2) and ``S^T q`` (2)."""
    return state_bytes(config) // 4 * 7


def gated_delta_least_s(config: Dict, state_rows: float,
                        peaks: Dict[str, float]) -> float:
    """The least time the state-update kernels of ``state_rows`` (slot,
    layer) pairs could take: the states' bytes in and out over the HBM
    peak, or their operations over the chip's peak, whichever is larger
    (the bytes, by two orders)."""
    return max(state_rows * 2 * state_bytes(config)
               / peaks["hbm_bytes_per_s"],
               state_rows * gated_delta_flops_per_row(config)
               / peaks["bf16_flops_per_s"])
