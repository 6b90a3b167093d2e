"""The MiMo family: how a configuration file becomes the program's
``FFModel`` graph (``flexflow_tpu/models/trinity.py``, the builder of
windowed and full layers by a list, with this line's answers as its
configuration), and how the reference's weights
(``benchmark/reference/mimo.py``) become the program's parameter tree.

The graph is built for inference: its matrices are stored in bfloat16,
once, and declared rather than drawn (the benchmark loads the seed's
weights before anything runs). ``to_program`` hands the program the
reference's OWN arrays (same dtype, same layouts, no reshape), so the
chip holds one copy of the weights while both are alive.

A configuration may be one holder's share of a larger deployment
(``reference/mimo.py``, "The share"): ``n_routed_experts`` experts held
from ``expert_first`` on, of ``published.n_routed_experts`` routed over.
"""

from __future__ import annotations

from typing import Dict

REFERENCE = "mimo"


def _dense_layers(config: Dict) -> int:
    """The leading dense layers: the builder's are a prefix."""
    freq = [int(k) for k in config["moe_layer_freq"]]
    dense = freq.index(1) if 1 in freq else len(freq)
    if any(k == 0 for k in freq[dense:]):
        raise ValueError("moe_layer_freq: the dense layers here come first")
    return dense


def program_config(config: Dict):
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.models.trinity import FULL, SLIDING, TrinityConfig

    pub = config.get("published") or {}
    held = int(config["n_routed_experts"])
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("the MLPs here are gated SiLU's")
    if config.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("the router here scores by a sigmoid")
    if (config.get("rope_scaling") or {}).get("rope_type",
                                              "default") != "default":
        raise ValueError("the rotary positions here are unscaled")
    for key in ("n_group", "topk_group"):
        if int(config.get(key) or 1) != 1:
            raise ValueError(f"{key}: the selection here is over one group")
    if config.get("tie_word_embeddings") or config.get("attention_bias"):
        raise ValueError("the head here is a matrix of its own, and no "
                         "linear map has a bias")
    if config.get("n_shared_experts"):
        raise ValueError("this line has no shared expert")
    for swa, full in (("swa_num_attention_heads", "num_attention_heads"),
                      ("swa_head_dim", "head_dim"),
                      ("swa_v_head_dim", "v_head_dim")):
        if int(config.get(swa, config[full])) != int(config[full]):
            raise ValueError(f"{swa}: both kinds of layer here share {full}")
    if int(config.get("sliding_window_size", config["sliding_window"])) \
            != int(config["sliding_window"]):
        raise ValueError("sliding_window_size is sliding_window's other name")
    d = int(config["head_dim"])
    sinks = tuple(kind for kind, key in (
        (SLIDING, "add_swa_attention_sink_bias"),
        (FULL, "add_full_attention_sink_bias")) if config.get(key))
    return TrinityConfig(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        layer_types=tuple(SLIDING if int(k) else FULL
                          for k in config["hybrid_layer_pattern"]),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        num_kv_heads_sliding=int(config["swa_num_key_value_heads"]),
        head_dim=d, v_head_dim=int(config["v_head_dim"]),
        rotary_dim=int(d * float(config.get("partial_rotary_factor", 1.0))),
        window=int(config["sliding_window"]),
        rope_theta=float(config["swa_rope_theta"]),
        rope_theta_full=float(config["rope_theta"]),
        sink_layers=sinks,
        value_scale=float(config.get("attention_value_scale") or 0) or None,
        rms_eps=float(config.get("layernorm_epsilon", 1e-5)),
        num_dense=_dense_layers(config),
        dense_width=int(config["intermediate_size"]),
        expert_width=int(config["moe_intermediate_size"]),
        n_routed=int(pub.get("n_routed_experts", held)),
        experts_per_token=int(config["num_experts_per_tok"]),
        routed_scale=float(config.get("routed_scaling_factor") or 1.0),
        norm_topk=bool(config.get("norm_topk_prob", True)),
        n_shared=0, experts_held=(int(config.get("expert_first", 0)), held),
        scale_embedding=False, sandwich_norms=False, qk_norm=False,
        gate=False, param_dtype=DataType.BFLOAT16, draw_weights=False)


def build(ff, config: Dict, batch: int, seq: int) -> None:
    """Add the model's layers to ``ff`` through ``models/trinity.py``."""
    from flexflow_tpu.models.trinity import build_trinity_lm

    if seq > int(config["max_position_embeddings"]):
        raise ValueError(f"{seq} positions exceed the model's "
                         f"{config['max_position_embeddings']}")
    build_trinity_lm(ff, batch, seq, program_config(config))


_ATTN = ("wq", "wk", "wv", "wo", "sinks")
_NORMS = ("norm_in", "norm_pre_mlp")
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
        out[f"block{i}_attn"] = {k: w[p + k] for k in _ATTN if p + k in w}
        if p + "mlp.gate" in w:
            out[f"block{i}_mlp"] = {k: w[p + "mlp." + k] for k in _MLP}
            continue
        out[f"block{i}_experts"] = {
            "router": w[p + "router"], "bias": w[p + "bias"],
            "w_gate": w[p + "experts.gate"], "w_up": w[p + "experts.up"],
            "w_down": w[p + "experts.down"]}
    return out


def expert_layer_names(config: Dict):
    """The program's routed-experts ops, in layer order."""
    return [f"block{i}_experts"
            for i in range(_dense_layers(config),
                           int(config["num_hidden_layers"]))]


# ---- what the readers ask of a family ------------------------------------------
# (``families/trinity.py`` says how: a function answers None where the
# window holds no such counters.)


def decode_step_least_s(run: Dict):
    """``decode_step_roofline``: every matrix read once in bfloat16 (of
    the held experts only the share that got a row: the window's
    ``stats()["moe"]``) and every visible row's keys and values once (a
    windowed layer's ``min(length + 1, window)`` rows a slot at 8 heads,
    a full layer's all at 4: the window's ``stats()["kv"]["window"]``),
    ``counts_mimo.decode_bytes_per_step``, over the HBM peak."""
    from benchmark import counts_mimo, routed_chunked, routed_window

    hit = routed_window.expert_hit_share(run)
    rows = routed_chunked.window_rows(run)
    if hit is None or rows is None:
        return None
    return (counts_mimo.decode_bytes_per_step(
        run["config"], rows["rows_read"] / rows["steps"],
        rows["rows_full"] / rows["steps"], hit)
        / run["peaks"]["hbm_bytes_per_s"])


def _attend_least_s(run: Dict, rows_key: str, count):
    from benchmark import routed_chunked

    rows = routed_chunked.window_rows(run)
    if rows is None:
        return None
    return (count(run["config"], rows[rows_key] / rows["steps"])
            / run["peaks"]["hbm_bytes_per_s"])


def full_attend_least_s(run: Dict):
    """``full_attention_roofline``: the live rows' keys and values of the
    full layers once a step (the window's ``rows_full``, rows to each
    slot's length, no block padding), ``counts_mimo.full_attend_bytes``,
    over the HBM peak."""
    from benchmark import counts_mimo

    return _attend_least_s(run, "rows_full", counts_mimo.full_attend_bytes)


def window_attend_least_s(run: Dict):
    """``sink_window_attention_roofline``: the visible rows' keys and
    values of the windowed layers once a step (the window's ``rows_read``:
    ``min(length + 1, window)`` a slot),
    ``counts_mimo.window_attend_bytes``, over the HBM peak."""
    from benchmark import counts_mimo

    return _attend_least_s(run, "rows_read", counts_mimo.window_attend_bytes)


def last_chunks(run: Dict):
    """The window's chunks that were their prompt's last, the only ones
    whose last layer attends: ``tokens`` and ``keys`` (their full-layer
    (query, seen key) pairs), from the loop's counters; None from a
    program that does not count them."""
    f = run["facts"]
    a = (f.get("stats0") or {}).get("loop") or {}
    b = (f.get("stats1") or {}).get("loop") or {}
    if "prefill_keys_last" not in a or "prefill_keys_last" not in b:
        return None
    return {"tokens": b["prefill_tokens_last"] - a["prefill_tokens_last"],
            "keys": b["prefill_keys_last"] - a["prefill_keys_last"]}


def chunk_least_s(run: Dict):
    """``prefill_chunk_mfu``: every fixed matrix once a live token (the
    window's ``prefill_tokens``; the last layer's behind its write once a
    token of a prompt's last chunk), the held experts' matrices once a
    pair the routing named among them (``prompt_pairs_held``), and the
    scores and weighted sums of the keys each query sees (``prefill_keys``
    in a full layer, ``prefill_keys_window`` in a windowed one,
    ``prefill_keys_last`` in the last) at keys of ``head_dim`` and values
    of ``v_head_dim``, ``counts_mimo.chunk_flops`` over the window's
    chunks, over the bfloat16 peak."""
    from benchmark import counts_mimo, routed_chunked

    n, last = routed_chunked.chunks(run), last_chunks(run)
    if n is None or last is None:
        return None
    return (counts_mimo.chunk_flops(
        run["config"], n["tokens"], n["pairs_held"], n["keys_full"],
        n["keys_window"], last["tokens"], last["keys"])
        / n["chunks"] / run["peaks"]["bf16_flops_per_s"])


def chunk_attention_least_s(run: Dict):
    """``chunk_attention_mfu``: the chunk's attention alone,
    ``counts_mimo.chunk_attention_flops`` over the window's chunks, over
    the bfloat16 peak."""
    from benchmark import counts_mimo, routed_chunked

    n, last = routed_chunked.chunks(run), last_chunks(run)
    if n is None or last is None:
        return None
    return (counts_mimo.chunk_attention_flops(
        run["config"], n["keys_full"], n["keys_window"], last["keys"])
        / n["chunks"] / run["peaks"]["bf16_flops_per_s"])
