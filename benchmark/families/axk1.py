"""The A.X-K1 family: how a configuration file becomes the program's
``FFModel`` graph (``flexflow_tpu/models/latent_moe.py``), and how the
reference's weights (``benchmark/reference/axk1.py``) become the program's
parameter tree.

The graph is built for inference: its matrices are stored in bfloat16,
once, and declared rather than drawn (the benchmark loads the seed's
weights before anything runs). ``to_program`` hands the program the
reference's OWN arrays — same dtype, same 2-D layouts, no reshape — so
the chip holds one copy of the weights while both are alive.

A configuration may be one holder's share of a larger deployment
(``reference/axk1.py``, "The share"): ``n_routed_experts`` experts held
from ``expert_first`` on, of ``published.n_routed_experts`` routed over.
"""

from __future__ import annotations

from typing import Dict

REFERENCE = "axk1"


def program_config(config: Dict, max_positions: int):
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.models.latent_moe import LatentMoEConfig

    pub = config.get("published") or {}
    held = int(config["n_routed_experts"])
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("the gated MLP here is SiLU's")
    if config.get("attention_bias"):
        raise ValueError("latent attention here has no biases")
    return LatentMoEConfig(
        vocab_size=int(config["vocab_size"]),
        max_positions=int(max_positions),
        hidden_size=int(config["hidden_size"]),
        num_layers=int(config["num_hidden_layers"]),
        num_heads=int(config["num_attention_heads"]),
        q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        rope_theta=float(config.get("rope_theta", 10000.0)),
        rope_scaling=config.get("rope_scaling"),
        rms_eps=float(config.get("rms_norm_eps", 1e-6)),
        first_dense=int(config["first_k_dense_replace"]),
        dense_width=int(config["intermediate_size"]),
        expert_width=int(config["moe_intermediate_size"]),
        n_routed=int(pub.get("n_routed_experts", held)),
        experts_per_token=int(config["num_experts_per_tok"]),
        n_group=int(config.get("n_group") or 1),
        topk_group=int(config.get("topk_group") or config.get("n_group")
                       or 1),
        scoring=config.get("scoring_func", "sigmoid"),
        norm_topk=bool(config.get("norm_topk_prob", True)),
        routed_scale=float(config.get("routed_scaling_factor", 1.0)),
        n_shared=int(config.get("n_shared_experts", 0)),
        experts_held=(int(config.get("expert_first", 0)), held),
        param_dtype=DataType.BFLOAT16, draw_weights=False)


def build(ff, config: Dict, batch: int, seq: int) -> None:
    """Add the model's layers to ``ff`` through ``models/latent_moe.py``."""
    from flexflow_tpu.models.latent_moe import build_latent_moe_lm

    if seq > int(config["max_position_embeddings"]):
        raise ValueError(f"{seq} positions exceed the model's "
                         f"{config['max_position_embeddings']}")
    build_latent_moe_lm(ff, batch, seq, program_config(config, seq))


def to_program(weights: Dict, config: Dict) -> Dict[str, Dict]:
    """Reference weights -> ``{op name: {weight name: array}}`` as
    ``CompiledModel.params`` holds them: the same arrays, renamed."""
    w = weights
    out = {"embed": {"weight": w["embed"]}, "norm_f": {"scale": w["norm_f"]},
           "lm_head": {"kernel": w["lm_head"]}}
    for i in range(int(config["num_hidden_layers"])):
        p = f"l{i}."
        out[f"block{i}_norm1"] = {"scale": w[p + "norm1"]}
        out[f"block{i}_norm2"] = {"scale": w[p + "norm2"]}
        out[f"block{i}_attn"] = {k: w[p + k] for k in (
            "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo")}
        if i < int(config["first_k_dense_replace"]):
            out[f"block{i}_mlp"] = {k: w[p + "mlp." + k]
                                    for k in ("gate", "up", "down")}
            continue
        out[f"block{i}_experts"] = {
            "router": w[p + "router"], "w_gate": w[p + "experts.gate"],
            "w_up": w[p + "experts.up"], "w_down": w[p + "experts.down"]}
        if int(config.get("n_shared_experts", 0)):
            out[f"block{i}_shared"] = {k: w[p + "shared." + k]
                                       for k in ("gate", "up", "down")}
    return out


def expert_layer_names(config: Dict):
    """The program's routed-experts ops, in layer order."""
    return [f"block{i}_experts"
            for i in range(int(config["first_k_dense_replace"]),
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
    ``stats()["moe"]``) and every live latent row once in every layer
    (the live tokens counted low from the window's ``blocks_read``),
    ``counts_latent_moe.decode_bytes_per_step``, over the HBM peak."""
    from benchmark import counts_latent_moe, routed_window

    hit = routed_window.expert_hit_share(run)
    live = routed_window.live_tokens_per_step(run)
    if hit is None or live is None:
        return None
    return (counts_latent_moe.decode_bytes_per_step(run["config"], live,
                                                    hit)
            / run["peaks"]["hbm_bytes_per_s"])
