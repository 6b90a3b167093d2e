"""The Ling family (``bailing_hybrid``): how a configuration file becomes
the program's ``FFModel`` graph (``flexflow_tpu/models/latent_moe.py``, the
builder of latent-attention layers and routed experts, with a layer
pattern: Kimi-Delta-Attention layers to one latent layer), and how the
reference's weights (``benchmark/reference/ling.py``) become the program's
parameter tree.

The graph is built for inference: its matrices are stored in bfloat16,
once, and declared rather than drawn (the benchmark loads the seed's
weights before anything runs). ``to_program`` hands the program the
reference's OWN arrays (same dtype, same 2-D layouts, no reshape), so the
chip holds one copy of the weights while both are alive.

A configuration may be one holder's share of a larger deployment
(``reference/ling.py``, "The share"): ``num_experts`` experts held from
``expert_first`` on, of ``published.num_experts`` routed over, and
``num_hidden_layers`` published layers from ``first_layer`` on.

The family refuses what it does not implement instead of guessing it: a
published key outside :data:`KNOWN`, an answer another than :data:`FIXED`'s
to a key whose answer the equations fix, a non-zero SwiGLU clamp in a kept
layer.
"""

from __future__ import annotations

from typing import Dict

REFERENCE = "ling"

# the answers the equations of ``reference/ling.py`` are written for
FIXED = {
    "model_type": "bailing_hybrid", "hidden_act": "silu",
    "gated_attention_proj_granularity_type": "head_wise",
    "group_norm_size": 1, "kda_safe_gate": True, "linear_silu": True,
    "moe_router_enable_expert_bias": True, "no_kda_lora": True,
    "norm_topk_prob": True, "num_kv_heads_for_linear_attn": 0,
    "q_lora_rank": None, "rope_interleave": True, "rope_scaling": None,
    "scale_router_input": False, "score_function": "sigmoid",
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_method": "noaux_tc", "up_proj_norm": False, "use_bias": False,
    "use_kda_lora": False, "use_mla_nope": False, "use_nGPT": False,
    "use_qk_norm": True, "use_qkv_bias": False, "value_norm": False,
    "num_shared_experts": 1,
}
# the keys the program config reads a size or a constant from
READ = (
    "first_k_dense_replace", "head_dim", "hidden_size", "intermediate_size",
    "kda_lower_bound", "kv_lora_rank", "layer_group_size",
    "max_position_embeddings", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "n_group", "num_attention_heads",
    "num_experts", "num_experts_per_tok", "num_hidden_layers",
    "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps", "rope_theta",
    "routed_scaling_factor", "short_conv_kernel_size", "topk_group",
    "v_head_dim", "vocab_size", "expert_swiglu_limit_list",
    "share_expert_swiglu_limit_list", "num_nextn_predict_layers")
# published keys that follow from others here, or say nothing about the
# forward (each is a line of the configuration's ``assumed`` or ``left_out``)
IGNORED = (
    "max_window_layers", "mtp_loss_scaling_factor", "mtp_use_kda",
    "num_key_value_heads", "partial_rotary_factor", "qk_head_dim",
    "rotary_dim", "seq_aux")
# what a benchmark's configuration file adds to the published keys
OWN = ("name", "source", "family", "reduced", "published", "expert_first",
       "first_layer", "deployment", "assumed", "left_out", "limits",
       "routing_check", "state_check", "limits_why")
KNOWN = frozenset(FIXED) | frozenset(READ) | frozenset(IGNORED) | frozenset(OWN)


def check(config: Dict) -> None:
    """Refuse a configuration this family does not implement."""
    unknown = sorted(set(config) - KNOWN)
    if unknown:
        raise ValueError(f"the Ling family implements no key {unknown}")
    for key, want in FIXED.items():
        if key in config and config[key] != want:
            raise ValueError(f"{key}: {config[key]!r}; the equations here "
                             f"are written for {want!r}")
    qk = int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])
    if int(config.get("qk_head_dim", qk)) != qk:
        raise ValueError("qk_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    if int(config.get("num_nextn_predict_layers", 0)):
        raise ValueError("num_nextn_predict_layers: no multi-token "
                         "prediction layer is built here")
    first = int(config.get("first_layer", 0))
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        kept = list(config.get(key) or [])[first:first
                                           + int(config["num_hidden_layers"])]
        if any(float(limit) != 0 for limit in kept):
            raise ValueError(f"{key}: a kept layer has a non-zero SwiGLU "
                             f"limit ({kept}); the clamp's form is not "
                             f"written here")


def layer_types(config: Dict):
    """Each held layer's mixer by its published index: latent attention
    where ``(p + 1) % layer_group_size == 0``, else KDA."""
    first = int(config.get("first_layer", 0))
    period = int(config["layer_group_size"])
    return tuple("latent" if (p + 1) % period == 0 else "kda"
                 for p in range(first,
                                first + int(config["num_hidden_layers"])))


def program_config(config: Dict, max_positions: int):
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.models.latent_moe import LatentMoEConfig

    check(config)
    pub = config.get("published") or {}
    held = int(config["num_experts"])
    return LatentMoEConfig(
        vocab_size=int(config["vocab_size"]),
        max_positions=int(max_positions),
        hidden_size=int(config["hidden_size"]),
        num_layers=int(config["num_hidden_layers"]),
        num_heads=int(config["num_attention_heads"]),
        q_lora_rank=None, kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        rope_theta=float(config["rope_theta"]), rope_scaling=None,
        rms_eps=float(config["rms_norm_eps"]),
        first_dense=int(config["first_k_dense_replace"]),
        dense_width=int(config["intermediate_size"]),
        expert_width=int(config["moe_intermediate_size"]),
        n_routed=int(pub.get("num_experts", held)),
        experts_per_token=int(config["num_experts_per_tok"]),
        n_group=int(config["n_group"]), topk_group=int(config["topk_group"]),
        scoring="sigmoid", norm_topk=True,
        routed_scale=float(config["routed_scaling_factor"]),
        n_shared=1, experts_held=(int(config.get("expert_first", 0)), held),
        selection_bias=True, layer_types=layer_types(config),
        first_layer=int(config.get("first_layer", 0)), output_gate="head",
        rope_interleaved=True, kda_head_dim=int(config["head_dim"]),
        kda_conv_taps=int(config["short_conv_kernel_size"]),
        kda_lower_bound=float(config["kda_lower_bound"]),
        param_dtype=DataType.BFLOAT16, draw_weights=False)


def build(ff, config: Dict, batch: int, seq: int) -> None:
    """Add the model's layers to ``ff`` through ``models/latent_moe.py``."""
    from flexflow_tpu.models.latent_moe import build_latent_moe_lm

    if seq > int(config["max_position_embeddings"]):
        raise ValueError(f"{seq} positions exceed the model's "
                         f"{config['max_position_embeddings']}")
    if (int(config["moe_shared_expert_intermediate_size"])
            != int(config["moe_intermediate_size"])):
        raise ValueError("the builder's shared expert is as wide as a "
                         "routed one")
    build_latent_moe_lm(ff, batch, seq, program_config(config, seq))


_KDA = ("wq", "wk", "wv", "wf", "wb", "wg", "conv", "a_log", "dt_bias",
        "norm", "wo")
_LATENT = ("wq", "wkv_a", "kv_norm", "wkv_b", "wg", "wo")
_MLP = ("gate", "up", "down")


def _dense(config: Dict, i: int) -> bool:
    return (int(config.get("first_layer", 0)) + i
            < int(config["first_k_dense_replace"]))


def to_program(weights: Dict, config: Dict) -> Dict[str, Dict]:
    """Reference weights -> ``{op name: {weight name: array}}`` as
    ``CompiledModel.params`` holds them: the same arrays, renamed."""
    w = weights
    out = {"embed": {"weight": w["embed"]}, "norm_f": {"scale": w["norm_f"]},
           "lm_head": {"kernel": w["lm_head"]}}
    for i, kind in enumerate(layer_types(config)):
        p = f"l{i}."
        out[f"block{i}_norm1"] = {"scale": w[p + "norm1"]}
        out[f"block{i}_norm2"] = {"scale": w[p + "norm2"]}
        out[f"block{i}_attn"] = {k: w[p + k]
                                 for k in (_KDA if kind == "kda" else _LATENT)}
        if _dense(config, i):
            out[f"block{i}_mlp"] = {k: w[p + "mlp." + k] for k in _MLP}
            continue
        out[f"block{i}_experts"] = {
            "router": w[p + "router"], "bias": w[p + "bias"],
            "w_gate": w[p + "experts.gate"], "w_up": w[p + "experts.up"],
            "w_down": w[p + "experts.down"]}
        out[f"block{i}_shared"] = {k: w[p + "shared." + k] for k in _MLP}
    return out


def expert_layer_names(config: Dict):
    """The program's routed-experts ops, in layer order."""
    return [f"block{i}_experts"
            for i in range(int(config["num_hidden_layers"]))
            if not _dense(config, i)]


def state_layer_names(config: Dict):
    """The program's KDA ops, in layer order."""
    return [f"block{i}_attn"
            for i, kind in enumerate(layer_types(config)) if kind == "kda"]


# ---- what the readers ask of a family ------------------------------------------
# ``run["family"]`` is this module (``benchmark/run.py``). A reader of a
# quantity that several families share takes from here what differs between
# them: which ``counts*.py`` the shapes are counted by, and which of the
# window's counters feed it. A function answers None where the window holds
# no such counters; a family that has no such quantity leaves the function
# out, and the reader then reports nothing.


def _window(run: Dict):
    """(live tokens, stepped states, hit share) a step, or None."""
    from benchmark import routed_window, state_window

    got = (routed_window.live_tokens_per_step(run),
           state_window.rows_per_step(run),
           routed_window.expert_hit_share(run))
    return None if any(g is None for g in got) else got


def decode_step_least_s(run: Dict):
    """``decode_step_roofline``: every matrix read once in bfloat16 (of
    the held experts only the share that got a row: the window's
    ``stats()["moe"]``), every stepped KDA state once in and once out at
    its float32 bytes (the window's ``rows_stepped`` a step) and every
    live token's latent row once in the latent layers (the live tokens
    counted low from the window's ``blocks_read``),
    ``counts_ling.decode_bytes_per_step``, over the HBM peak."""
    from benchmark import counts_ling

    got = _window(run)
    if got is None:
        return None
    live, rows, hit = got
    return (counts_ling.decode_bytes_per_step(run["config"], live, rows, hit)
            / run["peaks"]["hbm_bytes_per_s"])


def cache_bytes(run: Dict):
    """``state_bytes_share``: ``(state, rest)``, the window's
    ``rows_stepped`` times a KDA state's float32 bytes, in and out, beside
    its ``blocks_read`` times a block's latent rows over the latent layers
    (``counts_ling``)."""
    from benchmark import counts_ling, state_window

    return state_window.cache_bytes(run, counts_ling)


def state_step_least_s(run: Dict):
    """``kda_state_roofline``: the stepped states' bytes once in and once
    out over the HBM peak, ``counts_ling.state_step_least_s`` of the
    window's ``rows_stepped`` a step."""
    from benchmark import counts_ling, state_window

    rows = state_window.rows_per_step(run)
    if rows is None:
        return None
    return counts_ling.state_step_least_s(run["config"], rows, run["peaks"])
