"""What a model of state-space layers, latent expert layers and
grouped-head attention needs, counted from the configuration's shapes:
its parameters, what a request keeps, the bytes a decode step has to
move. The roofline shares divide these by measured device time; they live
here, with the benchmark, read the same work whatever implements it, and
are counted LOW (the embedding looked up and not read, gains, biases and
the convolutions' taps and tails left out of a step's bytes, the states
at their unpadded float32 bytes, the fewest live tokens the counters
prove, only the experts that got a row) so that no share can pass 100 %.

A configuration may be one holder's share and one stage of a pipeline
(``reference/nemotron_h.py``, "The share"): ``n_routed_experts`` is the
experts held, ``published.n_routed_experts`` the router's width, and
``hybrid_override_pattern`` the stage's own layers.

This PR writes no kernel: the ``M`` layers' chunked form and step, the
grouped expert product (batched products over a tile an expert) and the
attention of a prefill are XLA's; the paged decode kernel is the one the
benchmark has, with grouped heads. ``state_step_least_s`` is the least time of the
``M`` layers' step whoever computes it.
"""

from __future__ import annotations

from typing import Dict

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def _z(config: Dict) -> Dict:
    pub = config.get("published") or {}
    held = int(config["n_routed_experts"])
    pattern = str(config["hybrid_override_pattern"])
    h, p = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    g, n = int(config["n_groups"]), int(config["ssm_state_size"])
    return dict(
        v=int(config["vocab_size"]), e=int(config["hidden_size"]),
        m=pattern.count(MAMBA), x=pattern.count(EXPERTS),
        a=pattern.count(ATTENTION), layers=len(pattern),
        mh=h, mp=p, n=n, g=g, inner=h * p, channels=h * p + 2 * g * n,
        taps=int(config["conv_kernel"]),
        heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]),
        d=int(config["head_dim"]), held=held,
        routed=int(pub.get("n_routed_experts", held)),
        k=int(config["num_experts_per_tok"]),
        latent=int(config["moe_latent_size"]),
        we=int(config["moe_intermediate_size"]),
        ws=int(config["moe_shared_expert_intermediate_size"]))


def mamba_matrix_params(config: Dict) -> int:
    """``W_in`` (z, xBC, dt) and ``W_out`` of one ``M`` layer."""
    z = _z(config)
    return z["e"] * (z["inner"] + z["channels"] + z["mh"]) \
        + z["inner"] * z["e"]


def attention_matrix_params(config: Dict) -> int:
    z = _z(config)
    return 2 * z["e"] * z["heads"] * z["d"] \
        + 2 * z["e"] * z["kv_heads"] * z["d"]


def expert_params(config: Dict) -> int:
    """One routed expert's two matrices, inside the latent."""
    z = _z(config)
    return 2 * z["latent"] * z["we"]


def expert_layer_fixed_params(config: Dict) -> int:
    """What an ``E`` layer holds whatever the routing: the router, the
    latent projections and the shared expert."""
    z = _z(config)
    return (z["e"] * z["routed"] + 2 * z["e"] * z["latent"]
            + 2 * z["e"] * z["ws"])


def matrix_params(config: Dict, expert_hit_share: float = 1.0) -> float:
    """Parameters that sit in a matrix product of one decode step: every
    projection of every layer, the routers, the shared experts, the head,
    and of the held routed experts the share that got a row. The
    embedding is looked up row by row and is not among them."""
    z = _z(config)
    return (z["m"] * mamba_matrix_params(config)
            + z["a"] * attention_matrix_params(config)
            + z["x"] * (expert_layer_fixed_params(config)
                        + z["held"] * expert_params(config)
                        * expert_hit_share)
            + z["e"] * z["v"])


def param_count(config: Dict) -> int:
    """Every parameter the holder keeps: the matrices, the embedding, the
    selection biases, the convolutions' taps and biases, ``A_log``,
    ``dt_bias``, ``D`` and the norm gains."""
    z = _z(config)
    small_m = (z["taps"] + 1) * z["channels"] + 3 * z["mh"] + z["inner"]
    gains = z["layers"] * z["e"] + z["e"]
    return (int(matrix_params(config)) + z["v"] * z["e"] + gains
            + z["m"] * small_m + z["x"] * z["routed"])


def state_bytes(config: Dict) -> int:
    """One request's float32 state in ONE ``M`` layer, unpadded."""
    z = _z(config)
    return z["mh"] * z["mp"] * z["n"] * 4


def request_bytes(config: Dict, tail_bytes: int = 2) -> int:
    """What a request keeps over all ``M`` layers: the states and the
    convolutions' tails (``taps - 1`` positions of every channel)."""
    z = _z(config)
    return z["m"] * (state_bytes(config)
                     + (z["taps"] - 1) * z["channels"] * tail_bytes)


def kv_bytes_per_token(config: Dict, kv_bytes: int = 2) -> int:
    """Keys and values of one token over all ``*`` layers."""
    z = _z(config)
    return z["a"] * 2 * z["kv_heads"] * z["d"] * kv_bytes


def decode_bytes_per_step(config: Dict, live_tokens: float,
                          state_rows: float, expert_hit_share: float = 1.0,
                          weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one decode step has to move at the least: each matrix once,
    each stepped state once in and once out, each live token's keys and
    values once. ``state_rows``: active slots x ``M`` layers;
    ``live_tokens``: the sum over the active slots of the tokens cached."""
    return (matrix_params(config, expert_hit_share) * weight_bytes
            + state_rows * 2 * state_bytes(config)
            + live_tokens * kv_bytes_per_token(config, kv_bytes))


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
