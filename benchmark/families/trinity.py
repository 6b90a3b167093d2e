"""The Trinity family: how a configuration file becomes the program's
``FFModel`` graph (``flexflow_tpu/models/trinity.py``), and how the
reference's weights (``benchmark/reference/trinity.py``) become the
program's parameter tree.

The graph is built for inference: its matrices are stored in bfloat16,
once, and declared rather than drawn (the benchmark loads the seed's
weights before anything runs). ``to_program`` hands the program the
reference's OWN arrays (same dtype, same layouts, no reshape), so the
chip holds one copy of the weights while both are alive.

A configuration may be one holder's share of a larger deployment
(``reference/trinity.py``, "The share"): ``num_experts`` experts held
from ``expert_first`` on, of ``published.num_experts`` routed over.
"""

from __future__ import annotations

from typing import Dict

REFERENCE = "trinity"


def program_config(config: Dict):
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.models.trinity import TrinityConfig

    pub = config.get("published") or {}
    held = int(config["num_experts"])
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("the MLPs here are gated SiLU's")
    if config.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError("the router here scores by a sigmoid")
    if config.get("rope_scaling"):
        raise ValueError("the rotary positions here are unscaled")
    for key in ("n_group", "topk_group", "num_expert_groups",
                "num_limited_groups"):
        if int(config.get(key) or 1) != 1:
            raise ValueError(f"{key}: the selection here is over one group")
    if config.get("tie_word_embeddings"):
        raise ValueError("the head here is a matrix of its own")
    return TrinityConfig(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        layer_types=tuple(config["layer_types"]),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        window=int(config["sliding_window"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config.get("rms_norm_eps", 1e-5)),
        num_dense=int(config["num_dense_layers"]),
        dense_width=int(config["intermediate_size"]),
        expert_width=int(config["moe_intermediate_size"]),
        n_routed=int(pub.get("num_experts", held)),
        experts_per_token=int(config["num_experts_per_tok"]),
        routed_scale=float(config.get("route_scale", 1.0)),
        norm_topk=bool(config.get("route_norm", True)),
        n_shared=int(config.get("num_shared_experts", 1)),
        experts_held=(int(config.get("expert_first", 0)), held),
        scale_embedding=bool(config.get("mup_enabled", True)),
        param_dtype=DataType.BFLOAT16, draw_weights=False)


def build(ff, config: Dict, batch: int, seq: int) -> None:
    """Add the model's layers to ``ff`` through ``models/trinity.py``."""
    from flexflow_tpu.models.trinity import build_trinity_lm

    if seq > int(config["max_position_embeddings"]):
        raise ValueError(f"{seq} positions exceed the model's "
                         f"{config['max_position_embeddings']}")
    build_trinity_lm(ff, batch, seq, program_config(config))


_ATTN = ("wq", "wk", "wv", "wg", "wo", "q_norm", "k_norm")
_NORMS = ("norm_in", "norm_post_attn", "norm_pre_mlp", "norm_post_mlp")
_MLP = ("gate", "up", "down")


def to_program(weights: Dict, config: Dict) -> Dict[str, Dict]:
    """Reference weights -> ``{op name: {weight name: array}}`` as
    ``CompiledModel.params`` holds them: the same arrays, renamed."""
    w = weights
    out = {"embed": {"weight": w["embed"]}, "norm_f": {"scale": w["norm_f"]},
           "lm_head": {"kernel": w["lm_head"]}}
    for i in range(int(config["num_hidden_layers"])):
        p = f"l{i}."
        for n in _NORMS:
            out[f"block{i}_{n}"] = {"scale": w[p + n]}
        out[f"block{i}_attn"] = {k: w[p + k] for k in _ATTN}
        if i < int(config["num_dense_layers"]):
            out[f"block{i}_mlp"] = {k: w[p + "mlp." + k] for k in _MLP}
            continue
        out[f"block{i}_experts"] = {
            "router": w[p + "router"], "bias": w[p + "bias"],
            "w_gate": w[p + "experts.gate"], "w_up": w[p + "experts.up"],
            "w_down": w[p + "experts.down"]}
        if int(config.get("num_shared_experts", 1)):
            out[f"block{i}_shared"] = {k: w[p + "shared." + k] for k in _MLP}
    return out


def expert_layer_names(config: Dict):
    """The program's routed-experts ops, in layer order."""
    return [f"block{i}_experts"
            for i in range(int(config["num_dense_layers"]),
                           int(config["num_hidden_layers"]))]


# ---- what the readers ask of a family ------------------------------------------
# ``run["family"]`` is this module (``benchmark/run.py``). A reader of a
# quantity that several families share takes from here what differs between
# them: which ``counts*.py`` the shapes are counted by, and which of the
# window's counters feed it. A function answers None where the window holds
# no such counters; a family that has no such quantity leaves the function
# out, and the reader then reports nothing.


def decode_step_least_s(run: Dict):
    """``decode_step_roofline``: every matrix read once in bfloat16 (of
    the held experts only the share that got a row: the window's
    ``stats()["moe"]``) and every visible row's keys and values once (a
    windowed layer's ``min(length + 1, window)`` rows a slot, the full
    layer's all: the window's ``stats()["kv"]["window"]``),
    ``counts_trinity.decode_bytes_per_step``, over the HBM peak."""
    from benchmark import counts_trinity, routed_chunked, routed_window

    hit = routed_window.expert_hit_share(run)
    rows = routed_chunked.window_rows(run)
    if hit is None or rows is None:
        return None
    return (counts_trinity.decode_bytes_per_step(
        run["config"], rows["rows_read"] / rows["steps"],
        rows["rows_full"] / rows["steps"], hit)
        / run["peaks"]["hbm_bytes_per_s"])


def chunk_least_s(run: Dict):
    """``prefill_chunk_mfu``: every fixed matrix once a live token (the
    window's ``prefill_tokens``: padding counts for nothing), the held
    experts' matrices once a pair the routing named among them
    (``prompt_pairs_held``), and the scores and weighted sums of the keys
    each query sees (``prefill_keys`` in the full layer,
    ``prefill_keys_window`` in a windowed one),
    ``counts_trinity.chunk_flops`` over the window's chunks, over the
    bfloat16 peak."""
    from benchmark import counts_trinity, routed_chunked

    n = routed_chunked.chunks(run)
    if n is None:
        return None
    return (counts_trinity.chunk_flops(
        run["config"], n["tokens"], n["pairs_held"], n["keys_full"],
        n["keys_window"]) / n["chunks"] / run["peaks"]["bf16_flops_per_s"])
