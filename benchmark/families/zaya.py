"""The ZAYA family: how a configuration file becomes the program's
``FFModel`` graph (``flexflow_tpu/models/zaya.py``), and how the
reference's weights (``benchmark/reference/zaya.py``) become the
program's parameter tree.

The graph is built for inference: its matrices are stored in bfloat16,
once, and declared rather than drawn (the benchmark loads the seed's
weights before anything runs). ``to_program`` hands the program the
reference's OWN arrays (same dtype, same layouts, no reshape), so the
chip holds one copy of the weights while both are alive; the table is
held once, for the embedding and the head tied to it.

A configuration may be one stage of a pipeline (``reference/zaya.py``):
``num_hidden_layers`` its own layers from ``first_layer`` on.
"""

from __future__ import annotations

from typing import Dict

# imported here and not inside ``build``: a tree without the model fails
# the cell at once, before any weight is made or any program compiled
from flexflow_tpu.models import zaya as _program

REFERENCE = "zaya"


def program_config(config: Dict):
    from flexflow_tpu.ffconst import DataType

    rope = config["rope_parameters"]["hybrid"]
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("the experts here are gated SiLU's")
    if not config.get("tie_word_embeddings"):
        raise ValueError("the head here is the embedding's table")
    if config.get("sliding_window"):
        raise ValueError("every layer here sees all its keys")
    if rope.get("rope_type", "default") != "default":
        raise ValueError("the rotary positions here are unscaled")
    return _program.ZayaConfig(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        num_layers=int(config["num_hidden_layers"]),
        first_layer=int(config.get("first_layer", 0)),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        conv_taps=(int(config["cca_time0"]), int(config["cca_time1"])),
        rope_theta=float(rope["rope_theta"]),
        partial_rotary=float(rope["partial_rotary_factor"]),
        rms_eps=float(config.get("rms_norm_eps", 1e-5)),
        n_routed=int(config["num_experts"]),
        experts_per_token=int(config["num_experts_per_tok"]),
        expert_width=int(config["moe_intermediate_size"]),
        router_width=int(config["router_hidden_size"]),
        param_dtype=DataType.BFLOAT16, draw_weights=False)


def build(ff, config: Dict, batch: int, seq: int) -> None:
    """Add the model's layers to ``ff`` through ``models/zaya.py``."""
    if seq > int(config["max_position_embeddings"]):
        raise ValueError(f"{seq} positions exceed the model's "
                         f"{config['max_position_embeddings']}")
    _program.build_zaya_lm(ff, batch, seq, program_config(config))


_ATTN = ("wq", "wk", "wv1", "wv2", "wo", "conv0", "conv0_b", "conv1",
         "conv1_b", "temp")
# the program's name of each of the expert op's weights
_EXPERTS = {"router_down": "router.down", "router_b": "router.b",
           "depth_scale": "router.depth", "router_norm": "router.norm",
           "router_w1": "router.w1", "router_b1": "router.b1",
           "router_w2": "router.w2", "router_b2": "router.b2",
           "router": "router.out", "bias": "bias", "w_gate": "experts.gate",
           "w_up": "experts.up", "w_down": "experts.down"}


def to_program(weights: Dict, config: Dict) -> Dict[str, Dict]:
    """Reference weights -> ``{op name: {weight name: array}}`` as
    ``CompiledModel.params`` holds them: the same arrays, renamed (the
    head borrows the embedding's table and holds nothing)."""
    w = weights
    out = {"embed": {"weight": w["embed"]}, "norm_f": {"scale": w["norm_f"]}}
    for i in range(int(config["num_hidden_layers"])):
        p = f"l{i}."
        out[f"block{i}_norm_attn"] = {"scale": w[p + "norm_attn"]}
        out[f"block{i}_norm_moe"] = {"scale": w[p + "norm_moe"]}
        out[f"block{i}_attn"] = {k: w[p + k] for k in _ATTN}
        out[f"block{i}_experts"] = {k: w[p + r] for k, r in _EXPERTS.items()
                                    if p + r in w}
        for res in ("res1", "res2"):
            out[f"block{i}_{res}_stream"] = {"scale": w[f"{p}{res}.a"],
                                             "shift": w[f"{p}{res}.b"]}
            out[f"block{i}_{res}_branch"] = {"scale": w[f"{p}{res}.c"],
                                             "shift": w[f"{p}{res}.d"]}
    return out


def expert_layer_names(config: Dict):
    """The program's routed-experts ops, in layer order."""
    return [f"block{i}_experts"
            for i in range(int(config["num_hidden_layers"]))]


# ---- what the readers ask of a family ------------------------------------------
# ``run["family"]`` is this module (``benchmark/run.py``); a function answers
# None where the window holds no such counters (``families/trinity.py``).


def decode_step_least_s(run: Dict):
    """``decode_step_roofline``: every matrix read once in bfloat16 (of
    the experts only the share that got a row: the window's
    ``stats()["moe"]``; the table once, as the head), every live token's
    keys and values once (counted low from the window's ``blocks_read``)
    and every stepped request's tail and half value once in and once out
    (``rows_stepped`` a step), ``counts_zaya.decode_bytes_per_step``, over
    the HBM peak."""
    from benchmark import counts_zaya, routed_window, state_window

    hit = routed_window.expert_hit_share(run)
    live = routed_window.live_tokens_per_step(run)
    rows = state_window.rows_per_step(run)
    if hit is None or live is None or rows is None:
        return None
    return (counts_zaya.decode_bytes_per_step(run["config"], live, rows, hit)
            / run["peaks"]["hbm_bytes_per_s"])


def chunk_least_s(run: Dict):
    """``prefill_chunk_mfu``: every fixed matrix once a live token (the
    window's ``prefill_tokens``), an expert's matrices once a pair the
    routing named (``prompt_pairs_held``), and the scores and weighted
    sums of the keys each query sees (``prefill_keys``) in every layer,
    ``counts_zaya.chunk_flops`` over the window's chunks, over the
    bfloat16 peak."""
    from benchmark import counts_zaya, routed_chunked

    n = routed_chunked.chunks(run)
    if n is None:
        return None
    return (counts_zaya.chunk_flops(run["config"], n["tokens"],
                                    n["pairs_held"], n["keys_full"])
            / n["chunks"] / run["peaks"]["bf16_flops_per_s"])


def cache_bytes(run: Dict):
    """``state_bytes_share``: ``(state, rest)``, the window's
    ``rows_stepped`` times a request's tail and half value in one layer,
    in and out, beside its ``blocks_read`` times a block's keys and
    values over the layers (``counts_zaya``)."""
    from benchmark import counts_zaya, state_window

    return state_window.cache_bytes(run, counts_zaya)


def cca_attend_least_s(run: Dict):
    """``cca_attention_roofline``: the live tokens' keys and values once
    a layer and step (counted low from the window's ``blocks_read``),
    ``counts_zaya.attend_bytes_per_step``, over the HBM peak: the least
    time of ONE decode step's ``attend`` scopes, all layers."""
    from benchmark import counts_zaya, routed_window

    live = routed_window.live_tokens_per_step(run)
    if live is None:
        return None
    return (counts_zaya.attend_bytes_per_step(run["config"], live)
            / run["peaks"]["hbm_bytes_per_s"])
