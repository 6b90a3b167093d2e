"""The Nemotron-H family: how a configuration file becomes the program's
``FFModel`` graph (``flexflow_tpu/models/nemotron_h.py``), and how the
reference's weights (``benchmark/reference/nemotron_h.py``) become the
program's parameter tree.

The graph is built for inference: its matrices are stored in bfloat16,
once, and declared rather than drawn (the benchmark loads the seed's
weights before anything runs). ``to_program`` hands the program the
reference's OWN arrays (same dtype, same layouts, no reshape), so the
chip holds one copy of the weights while both are alive.

A configuration may be one holder's share of a larger deployment
(``reference/nemotron_h.py``, "The share"): ``n_routed_experts`` experts
held from ``expert_first`` on, of ``published.n_routed_experts`` routed
over.
"""

from __future__ import annotations

from typing import Dict

REFERENCE = "nemotron_h"


def program_config(config: Dict):
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.models.nemotron_h import NemotronHConfig

    pub = config.get("published") or {}
    held = int(config["n_routed_experts"])
    if config.get("mlp_hidden_act", "relu2") != "relu2":
        raise ValueError("the MLPs here are squared-ReLU's")
    for key in ("use_bias", "mlp_bias", "attention_bias", "mamba_proj_bias"):
        if config.get(key):
            raise ValueError(f"{key}: the linear maps here have no biases")
    if not config.get("use_conv_bias", True):
        raise ValueError("the convolution here has a bias")
    if int(config.get("n_group") or 1) != 1:
        raise ValueError("the selection here is over one group")
    if (int(config["num_attention_heads"]) * int(config["head_dim"])
            != int(config["hidden_size"])):
        raise ValueError("the attention op's heads are hidden / heads wide")
    return NemotronHConfig(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        pattern=str(config["hybrid_override_pattern"]),
        rms_eps=float(config.get("layer_norm_epsilon", 1e-5)),
        mamba_heads=int(config["mamba_num_heads"]),
        mamba_head_dim=int(config["mamba_head_dim"]),
        state_size=int(config["ssm_state_size"]),
        n_groups=int(config["n_groups"]),
        conv_taps=int(config["conv_kernel"]),
        chunk_size=int(config["chunk_size"]),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        n_routed=int(pub.get("n_routed_experts", held)),
        experts_per_token=int(config["num_experts_per_tok"]),
        routed_scale=float(config.get("routed_scaling_factor", 1.0)),
        norm_topk=bool(config.get("norm_topk_prob", True)),
        latent_size=int(config["moe_latent_size"]),
        expert_width=int(config["moe_intermediate_size"]),
        shared_width=int(config["moe_shared_expert_intermediate_size"]),
        experts_held=(int(config.get("expert_first", 0)), held),
        param_dtype=DataType.BFLOAT16, draw_weights=False)


def build(ff, config: Dict, batch: int, seq: int) -> None:
    """Add the model's layers to ``ff`` through ``models/nemotron_h.py``."""
    from flexflow_tpu.models.nemotron_h import build_nemotron_h_lm

    if seq > int(config["max_position_embeddings"]):
        raise ValueError(f"{seq} positions exceed the model's "
                         f"{config['max_position_embeddings']}")
    build_nemotron_h_lm(ff, batch, seq, program_config(config))


_MAMBA = {"w_in": "w_in", "conv": "conv", "conv_bias": "conv_bias",
          "a_log": "a_log", "dt_bias": "dt_bias", "d": "d",
          "norm": "gate_norm", "w_out": "w_out"}
_EXPERTS = {"router": "router", "bias": "bias",
            "latent_down": "latent_down", "latent_up": "latent_up",
            "w_up": "experts.up", "w_down": "experts.down"}


def to_program(weights: Dict, config: Dict) -> Dict[str, Dict]:
    """Reference weights -> ``{op name: {weight name: array}}`` as
    ``CompiledModel.params`` holds them: the same arrays, renamed."""
    w = weights
    out = {"embed": {"weight": w["embed"]}, "norm_f": {"scale": w["norm_f"]},
           "lm_head": {"kernel": w["lm_head"]}}
    for i, kind in enumerate(str(config["hybrid_override_pattern"])):
        p = f"l{i}."
        out[f"block{i}_norm"] = {"scale": w[p + "norm"]}
        if kind == "M":
            out[f"block{i}_mixer"] = {k: w[p + v] for k, v in _MAMBA.items()}
        elif kind == "E":
            out[f"block{i}_mixer"] = {k: w[p + v]
                                      for k, v in _EXPERTS.items()}
            out[f"block{i}_shared_up"] = {"kernel": w[p + "shared.up"]}
            out[f"block{i}_shared_down"] = {"kernel": w[p + "shared.down"]}
        else:
            out[f"block{i}_mixer"] = {k: w[p + k]
                                      for k in ("wq", "wk", "wv", "wo")}
    return out


def expert_layer_names(config: Dict):
    """The program's routed-experts ops, in layer order."""
    return [f"block{i}_mixer"
            for i, kind in enumerate(str(config["hybrid_override_pattern"]))
            if kind == "E"]


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
    ``stats()["moe"]``), every stepped state once in and once out (the
    window's ``rows_stepped`` a step) and every live token's keys and
    values once (the live tokens counted low from the window's
    ``blocks_read``), ``counts_nemotron_h.decode_bytes_per_step``, over
    the HBM peak."""
    from benchmark import counts_nemotron_h, routed_window, state_window

    hit = routed_window.expert_hit_share(run)
    live = routed_window.live_tokens_per_step(run)
    rows = state_window.rows_per_step(run)
    if hit is None or live is None or rows is None:
        return None
    return (counts_nemotron_h.decode_bytes_per_step(
        run["config"], live, rows, hit) / run["peaks"]["hbm_bytes_per_s"])


def cache_bytes(run: Dict):
    """``state_bytes_share``: ``(state, rest)``, the window's
    ``rows_stepped`` times a state's float32 bytes, in and out, beside its
    ``blocks_read`` times a block's keys and values over the ``*`` layers
    (``counts_nemotron_h``)."""
    from benchmark import counts_nemotron_h, state_window

    return state_window.cache_bytes(run, counts_nemotron_h)


def state_step_least_s(run: Dict):
    """``mamba_state_roofline``: the stepped states' bytes once in and
    once out over the HBM peak, ``counts_nemotron_h.state_step_least_s``
    of the window's ``rows_stepped`` a step."""
    from benchmark import counts_nemotron_h, state_window

    rows = state_window.rows_per_step(run)
    if rows is None:
        return None
    return counts_nemotron_h.state_step_least_s(run["config"], rows,
                                                run["peaks"])
