"""The Olmo-Hybrid family: how a configuration file becomes the program's
``FFModel`` graph (``flexflow_tpu/models/hybrid.py``), and how the
reference's weights (``benchmark/reference/olmo_hybrid.py``) become the
program's parameter tree.

The graph is built for inference: its matrices are stored in bfloat16,
once, and declared rather than drawn (the benchmark loads the seed's
weights before anything runs). ``to_program`` hands the program the
reference's OWN arrays — same dtype, same layouts, no reshape — so the
chip holds one copy of the weights while both are alive.

A configuration may be one stage of a pipeline: ``num_hidden_layers`` and
``layer_types`` are then the stage's (``published`` holds the model's),
with the embedding and the head on it.
"""

from __future__ import annotations

from typing import Dict

REFERENCE = "olmo_hybrid"
LINEAR = "linear_attention"


def program_config(config: Dict):
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.models.hybrid import HybridLMConfig

    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("the gated MLP here is SiLU's")
    if config.get("attention_bias"):
        raise ValueError("the full layers here have no biases")
    if config.get("tie_word_embeddings"):
        raise ValueError("the head here is a matrix of its own")
    heads = int(config["num_attention_heads"])
    if int(config.get("num_key_value_heads", heads)) != heads:
        raise ValueError("grouped key-value heads are not built here")
    if (config.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError("the full layers here take no rotary embedding")
    lin_heads = int(config["linear_num_value_heads"])
    if int(config["linear_num_key_heads"]) != lin_heads:
        raise ValueError("linear layers with fewer key heads than value "
                         "heads are not built here")
    types = tuple(config["layer_types"])
    if len(types) != int(config["num_hidden_layers"]):
        raise ValueError(f"{len(types)} layer_types for "
                         f"{config['num_hidden_layers']} layers")
    return HybridLMConfig(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]), layer_types=types,
        num_heads=heads, linear_heads=lin_heads,
        linear_key_dim=int(config["linear_key_head_dim"]),
        linear_value_dim=int(config["linear_value_head_dim"]),
        conv_taps=int(config["linear_conv_kernel_dim"]),
        allow_neg_eigval=bool(config.get("linear_allow_neg_eigval", False)),
        mlp_width=int(config["intermediate_size"]),
        rms_eps=float(config.get("rms_norm_eps", 1e-6)),
        param_dtype=DataType.BFLOAT16, draw_weights=False)


def build(ff, config: Dict, batch: int, seq: int) -> None:
    """Add the model's layers to ``ff`` through ``models/hybrid.py``."""
    from flexflow_tpu.models.hybrid import build_hybrid_lm

    if seq > int(config["max_position_embeddings"]):
        raise ValueError(f"{seq} positions exceed the model's "
                         f"{config['max_position_embeddings']}")
    build_hybrid_lm(ff, batch, seq, program_config(config))


def to_program(weights: Dict, config: Dict) -> Dict[str, Dict]:
    """Reference weights -> ``{op name: {weight name: array}}`` as
    ``CompiledModel.params`` holds them: the same arrays, renamed."""
    w = weights
    out = {"embed": {"weight": w["embed"]}, "norm_f": {"scale": w["norm_f"]},
           "lm_head": {"kernel": w["lm_head"]}}
    for i, kind in enumerate(config["layer_types"]):
        p = f"l{i}."
        names = (("wq", "wk", "wv", "wg", "wa", "wb", "conv", "a_log",
                  "dt_bias", "norm", "wo") if kind == LINEAR else
                 ("wq", "wk", "wv", "wo", "q_norm", "k_norm"))
        out[f"block{i}_mixer"] = {k: w[p + k] for k in names}
        out[f"block{i}_norm1"] = {"scale": w[p + "norm1"]}
        out[f"block{i}_norm2"] = {"scale": w[p + "norm2"]}
        out[f"block{i}_mlp"] = {k: w[p + "mlp." + k]
                                for k in ("gate", "up", "down")}
    return out


# ---- what the readers ask of a family ------------------------------------------
# ``run["family"]`` is this module (``benchmark/run.py``). A reader of a
# quantity that several families share takes from here what differs between
# them: which ``counts*.py`` the shapes are counted by, and which of the
# window's counters feed it. A function answers None where the window holds
# no such counters; a family that has no such quantity leaves the function
# out, and the reader then reports nothing.


def decode_step_least_s(run: Dict):
    """``decode_step_roofline``: every matrix read once in bfloat16, the
    state of every active slot and linear layer once in and once out at
    its unpadded float32 bytes (the window's ``rows_stepped``), every live
    token's keys and values once (counted low from the window's
    ``blocks_read``), ``counts_hybrid.decode_bytes_per_step``, over the
    HBM peak."""
    from benchmark import counts_hybrid, routed_window, state_window

    rows = state_window.rows_per_step(run)
    live = routed_window.live_tokens_per_step(run)
    if rows is None or live is None:
        return None
    return (counts_hybrid.decode_bytes_per_step(run["config"], live, rows)
            / run["peaks"]["hbm_bytes_per_s"])


def cache_bytes(run: Dict):
    """``state_bytes_share``: ``(state, rest)``, the window's
    ``rows_stepped`` times a state's float32 bytes, in and out, beside its
    ``blocks_read`` times a block's keys and values over the full layers
    (``counts_hybrid``)."""
    from benchmark import counts_hybrid, state_window

    return state_window.cache_bytes(run, counts_hybrid)
