"""The Granite 4.0-H family: how a configuration file becomes the
program's ``FFModel`` graph (``flexflow_tpu/models/granite_hybrid.py``),
and how the reference's weights (``benchmark/reference/
granite_hybrid.py``) become the program's parameter tree.

The graph is built for inference: its matrices are stored in bfloat16,
once, and declared rather than drawn (the benchmark loads the seed's
weights before anything runs). ``to_program`` hands the program the
reference's OWN arrays (same dtype, same layouts, no reshape), so the
chip holds one copy of the weights while both are alive; the embedding
is handed over once: the program's head is tied to it and has no matrix
of its own.
"""

from __future__ import annotations

from typing import Dict

REFERENCE = "granite_hybrid"


def program_config(config: Dict):
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.models.granite_hybrid import GraniteHybridConfig

    for key in ("attention_bias", "mamba_proj_bias"):
        if config.get(key):
            raise ValueError(f"{key}: the linear maps here have no biases")
    if not config.get("mamba_conv_bias", True):
        raise ValueError("the convolution here has a bias")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("the MLPs here are gated SiLU's")
    if config.get("position_embedding_type", "nope") != "nope":
        raise ValueError("the attention layers here read no positions")
    if not config.get("tie_word_embeddings", True):
        raise ValueError("the head here is the embedding's table")
    if int(config.get("num_local_experts") or 0):
        raise ValueError("no routed experts here: the MLP is the shared one")
    if (int(config["mamba_expand"]) * int(config["hidden_size"])
            != int(config["mamba_n_heads"]) * int(config["mamba_d_head"])):
        raise ValueError("mamba_expand x hidden_size is not heads x d_head")
    return GraniteHybridConfig(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        layer_types=tuple(config["layer_types"]),
        rms_eps=float(config.get("rms_norm_eps", 1e-5)),
        mlp_width=int(config["shared_intermediate_size"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        mamba_heads=int(config["mamba_n_heads"]),
        mamba_head_dim=int(config["mamba_d_head"]),
        state_size=int(config["mamba_d_state"]),
        n_groups=int(config["mamba_n_groups"]),
        conv_taps=int(config["mamba_d_conv"]),
        chunk_size=int(config["mamba_chunk_size"]),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        param_dtype=DataType.BFLOAT16, draw_weights=False)


def build(ff, config: Dict, batch: int, seq: int) -> None:
    """Add the model's layers to ``ff`` through
    ``models/granite_hybrid.py``."""
    from flexflow_tpu.models.granite_hybrid import build_granite_hybrid_lm

    if seq > int(config["max_position_embeddings"]):
        raise ValueError(f"{seq} positions exceed the model's "
                         f"{config['max_position_embeddings']}")
    build_granite_hybrid_lm(ff, batch, seq, program_config(config))


_MAMBA = {"w_in": "w_in", "conv": "conv", "conv_bias": "conv_bias",
          "a_log": "a_log", "dt_bias": "dt_bias", "d": "d",
          "norm": "gate_norm", "w_out": "w_out"}


def to_program(weights: Dict, config: Dict) -> Dict[str, Dict]:
    """Reference weights -> ``{op name: {weight name: array}}`` as
    ``CompiledModel.params`` holds them: the same arrays, renamed; no
    ``lm_head`` entry (the head reads ``embed``'s)."""
    w = weights
    out = {"embed": {"weight": w["embed"]}, "norm_f": {"scale": w["norm_f"]}}
    for i, kind in enumerate(config["layer_types"]):
        p = f"l{i}."
        out[f"block{i}_norm"] = {"scale": w[p + "norm"]}
        out[f"block{i}_mlp_norm"] = {"scale": w[p + "mlp_norm"]}
        out[f"block{i}_mlp"] = {k: w[p + k] for k in ("gate", "up", "down")}
        out[f"block{i}_mixer"] = (
            {k: w[p + v] for k, v in _MAMBA.items()} if kind == "mamba"
            else {k: w[p + k] for k in ("wq", "wk", "wv", "wo")})
    return out


# ---- what the readers ask of a family ------------------------------------------
# ``run["family"]`` is this module (``benchmark/run.py``). A reader of a
# quantity that several families share takes from here what differs between
# them: which ``counts*.py`` the shapes are counted by, and which of the
# window's counters feed it. A function answers None where the window holds
# no such counters; a family that has no such quantity leaves the function
# out, and the reader then reports nothing.


def decode_step_least_s(run: Dict):
    """``decode_step_roofline``: every matrix read once in bfloat16 with
    the embedding once (as the head), every stepped state once in and
    once out (the window's ``rows_stepped`` a step) and every live
    token's keys and values once (the live tokens counted low from the
    window's ``blocks_read``),
    ``counts_granite_hybrid.decode_bytes_per_step``, over the HBM peak."""
    from benchmark import (counts_granite_hybrid, routed_window,
                           state_window)

    live = routed_window.live_tokens_per_step(run)
    rows = state_window.rows_per_step(run)
    if live is None or rows is None:
        return None
    return (counts_granite_hybrid.decode_bytes_per_step(
        run["config"], live, rows) / run["peaks"]["hbm_bytes_per_s"])


def cache_bytes(run: Dict):
    """``state_bytes_share``: ``(state, rest)``, the window's
    ``rows_stepped`` times a state's float32 bytes, in and out, beside its
    ``blocks_read`` times a block's keys and values over the four
    attention layers (``counts_granite_hybrid``)."""
    from benchmark import counts_granite_hybrid, state_window

    return state_window.cache_bytes(run, counts_granite_hybrid)


def state_step_least_s(run: Dict):
    """``mamba_state_roofline``: the stepped states' bytes once in and
    once out over the HBM peak,
    ``counts_granite_hybrid.state_step_least_s`` of the window's
    ``rows_stepped`` a step."""
    from benchmark import counts_granite_hybrid, state_window

    rows = state_window.rows_per_step(run)
    if rows is None:
        return None
    return counts_granite_hybrid.state_step_least_s(run["config"], rows,
                                                    run["peaks"])


def chunk_least_s(run: Dict):
    """``prefill_chunk_mfu``: every layer's matrices once a live token
    (the window's ``prefill_tokens``: padding counts for nothing), the
    scan's products by the published blocked form, and the scores and
    weighted sums of the keys each query sees (``prefill_keys``) in the
    four attention layers, ``counts_granite_hybrid.chunk_flops`` over the
    window's chunks, over the bfloat16 peak."""
    from benchmark import counts_granite_hybrid, plain_chunked

    n = plain_chunked.chunks(run)
    if n is None:
        return None
    return (counts_granite_hybrid.chunk_flops(
        run["config"], n["tokens"], n["keys"]) / n["chunks"]
        / run["peaks"]["bf16_flops_per_s"])
