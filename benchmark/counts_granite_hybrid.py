"""What a model of Mamba-2 and grouped-head attention blocks with a gated
MLP each and a tied head needs, counted from the configuration's shapes:
its parameters, what a request keeps, the bytes a decode step has to move
and the operations a prefill chunk has to do. The roofline shares divide
these by measured device time; they live here, with the benchmark, read
the same work whatever implements it, and are counted LOW (the embedding
read ONCE a step, as the head, and its looked-up rows not at all; gains,
biases and the convolutions' taps and tails left out of a step's bytes;
the states at their unpadded float32 bytes; the fewest live tokens the
counters prove; a chunk's live tokens only, its scan's products as the
published blocked form has them, only the keys a query sees, the head
for no token of a chunk) so that no share can pass 100 %.

This PR writes no kernel: the Mamba layers' blocked form and step and the
attention of a chunk (a walk over key spans: heads of 64 are narrower
than the chunk kernel's lane tile) are XLA's; a decode step's attention
is the paged kernel the benchmark has, which takes grouped heads of 64,
two a lane tile, since this PR.
"""

from __future__ import annotations

from typing import Dict

MAMBA, ATTENTION = "mamba", "attention"


def _z(config: Dict) -> Dict:
    kinds = list(config["layer_types"])
    h, p = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    g, n = int(config["mamba_n_groups"]), int(config["mamba_d_state"])
    heads = int(config["num_attention_heads"])
    return dict(
        v=int(config["vocab_size"]), e=int(config["hidden_size"]),
        m=kinds.count(MAMBA), a=kinds.count(ATTENTION), layers=len(kinds),
        mh=h, mp=p, n=n, g=g, inner=h * p, channels=h * p + 2 * g * n,
        taps=int(config["mamba_d_conv"]),
        block=int(config["mamba_chunk_size"]), heads=heads,
        kv_heads=int(config["num_key_value_heads"]),
        d=int(config["hidden_size"]) // heads,
        w=int(config["shared_intermediate_size"]))


def mamba_matrix_params(config: Dict) -> int:
    """``W_in`` (z, xBC, dt) and ``W_out`` of one Mamba mixer."""
    z = _z(config)
    return z["e"] * (z["inner"] + z["channels"] + z["mh"]) \
        + z["inner"] * z["e"]


def attention_matrix_params(config: Dict) -> int:
    z = _z(config)
    return 2 * z["e"] * z["heads"] * z["d"] \
        + 2 * z["e"] * z["kv_heads"] * z["d"]


def mlp_params(config: Dict) -> int:
    """Every block's gated MLP: three matrices."""
    z = _z(config)
    return 3 * z["e"] * z["w"]


def layer_matrix_params(config: Dict) -> int:
    """The matrices of all layers: mixers and MLPs."""
    z = _z(config)
    return (z["m"] * mamba_matrix_params(config)
            + z["a"] * attention_matrix_params(config)
            + z["layers"] * mlp_params(config))


def param_count(config: Dict) -> int:
    """Every parameter: the layers' matrices, the embedding ONCE (it is
    the head), the convolutions' taps and biases, ``A_log``, ``dt_bias``,
    ``D`` and the norm gains."""
    z = _z(config)
    small_m = (z["taps"] + 1) * z["channels"] + 3 * z["mh"] + z["inner"]
    gains = 2 * z["layers"] * z["e"] + z["e"]
    return (layer_matrix_params(config) + z["v"] * z["e"] + gains
            + z["m"] * small_m)


def state_bytes(config: Dict) -> int:
    """One request's float32 state in ONE Mamba layer, unpadded."""
    z = _z(config)
    return z["mh"] * z["mp"] * z["n"] * 4


def request_bytes(config: Dict, tail_bytes: int = 2) -> int:
    """What a request keeps over all Mamba layers: the states and the
    convolutions' tails (``taps - 1`` positions of every channel)."""
    z = _z(config)
    return z["m"] * (state_bytes(config)
                     + (z["taps"] - 1) * z["channels"] * tail_bytes)


def kv_bytes_per_token(config: Dict, kv_bytes: int = 2) -> int:
    """Keys and values of one token over all attention layers."""
    z = _z(config)
    return z["a"] * 2 * z["kv_heads"] * z["d"] * kv_bytes


def decode_bytes_per_step(config: Dict, live_tokens: float,
                          state_rows: float, weight_bytes: int = 2,
                          kv_bytes: int = 2) -> float:
    """Bytes one decode step has to move at the least: each matrix once
    with the embedding once (as the head), each stepped state once in and
    once out, each live token's keys and values once. ``state_rows``:
    active slots x Mamba layers; ``live_tokens``: the sum over the active
    slots of the tokens cached."""
    z = _z(config)
    return ((layer_matrix_params(config) + z["v"] * z["e"]) * weight_bytes
            + state_rows * 2 * state_bytes(config)
            + live_tokens * kv_bytes_per_token(config, kv_bytes))


def scan_flops(config: Dict, tokens: float) -> float:
    """The operations the recurrence of ALL Mamba layers needs for
    ``tokens`` live tokens by the published blocked form: within a block
    of ``mamba_chunk_size`` a position's ``C . B`` a group and the
    weighted sum a head over the block's positions (the form's own
    products, the masked half among them), and a head's read of the
    carried state and its share of the next one (``P N`` each)."""
    z = _z(config)
    per_token = (2.0 * z["block"] * (z["g"] * z["n"] + z["mh"] * z["mp"])
                 + 4.0 * z["mh"] * z["mp"] * z["n"])
    return z["m"] * tokens * per_token


def chunk_flops(config: Dict, tokens: float, keys: float) -> float:
    """The operations the window's prefill chunks need: the layers'
    matrices twice a live token, the scan's products, and a query's
    scores and weighted sum over the ``keys`` it sees (summed over the
    queries) in every attention layer."""
    z = _z(config)
    return (2.0 * tokens * layer_matrix_params(config)
            + scan_flops(config, tokens)
            + z["a"] * 4.0 * z["heads"] * z["d"] * keys)


def state_step_flops_per_row(config: Dict) -> int:
    """Operations of one state's update and read-out: per number the
    decay (1), ``dt x B^T`` (2) and ``S C`` (2)."""
    return state_bytes(config) // 4 * 5


def state_step_least_s(config: Dict, state_rows: float,
                       peaks: Dict[str, float]) -> float:
    """The least time the state updates of ``state_rows`` (slot, layer)
    pairs could take: the states' bytes in and out over the HBM peak, or
    their operations over the chip's peak, whichever is larger (the
    bytes, by two orders)."""
    return max(state_rows * 2 * state_bytes(config)
               / peaks["hbm_bytes_per_s"],
               state_rows * state_step_flops_per_row(config)
               / peaks["bf16_flops_per_s"])
