"""The GLM-5.3 family (``glm5_next_text``): how a configuration file becomes
the program's ``FFModel`` graph (``flexflow_tpu/models/latent_moe.py``, the
builder of latent-attention layers and routed experts, here with KDA
layers beside ONE sparse latent layer in four, an indexer, four residual
streams and the SwiGLU clamp), and how the reference's weights
(``benchmark/reference/glm.py``) become the program's parameter tree.

The graph is built for inference: its matrices are stored in bfloat16,
once, and declared rather than drawn. ``to_program`` hands the program the
reference's OWN arrays (same dtype, same 2-D layouts, no reshape), so the
chip holds one copy of the weights while both are alive.

A configuration may be one holder's share of a larger deployment
(``reference/glm.py``, "The share"): ``n_routed_experts`` experts held
from ``expert_first`` on, of ``published.n_routed_experts`` routed over,
and ``num_hidden_layers`` published layers from ``first_layer`` on, whose
``layer_types``, ``mlp_layer_types`` and ``indexer_types`` are the
published lists cut to those indices.

The family refuses what it does not implement instead of guessing it: a
key outside :data:`KNOWN`, an answer another than :data:`FIXED`'s to a key
whose answer the equations fix (the pools' form among them), a layer
pattern that is not the published one at the held indices.
"""

from __future__ import annotations

from typing import Dict

REFERENCE = "glm"

# the answers the equations of ``reference/glm.py`` are written for
FIXED = {
    "model_type": "glm5_next_text", "hidden_act": "silu",
    "attention_bias": False, "head_dim": 0,
    "index_kpool": 4, "index_kpool_compress": True,
    "index_kpool_always_select_tail": True,
    "indexer_rope_interleave": True, "mhc": True, "mla_use_nope": True,
    "qk_rope_head_dim": 0, "n_group": 1, "topk_group": 1,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "tie_word_embeddings": False,
}
# the keys the program config reads a size, a constant or a pattern from
READ = (
    "first_k_dense_replace", "hc_eps", "hc_mult", "hc_sinkhorn_iters",
    "hidden_size", "index_head_dim", "index_n_heads", "index_topk",
    "indexer_types", "intermediate_size", "kv_lora_rank", "layer_types",
    "linear_attn_config", "max_position_embeddings", "mlp_layer_types",
    "moe_intermediate_size", "n_routed_experts", "num_attention_heads",
    "num_experts_per_tok", "num_hidden_layers", "num_nextn_predict_layers",
    "q_lora_rank", "qk_nope_head_dim", "rms_norm_eps",
    "routed_scaling_factor", "swiglu_limit", "v_head_dim", "vocab_size")
# published keys that follow from others here, or say nothing about the
# forward of the layers kept (each is a line of ``assumed`` or ``left_out``)
IGNORED = ("index_share_for_mtp_iteration", "num_key_value_heads",
           "qk_head_dim")
# what a benchmark's configuration file adds to the published keys
OWN = ("name", "source", "family", "reduced", "published", "expert_first",
       "first_layer", "deployment", "assumed", "left_out", "limits",
       "routing_check", "selection_check", "state_check", "limits_why",
       "index_rope_dim", "index_rope_theta")
KNOWN = frozenset(FIXED) | frozenset(READ) | frozenset(IGNORED) | frozenset(OWN)
KDA, SPARSE = "linear_attention", "deepseek_sparse_attention"


def check(config: Dict) -> None:
    """Refuse a configuration this family does not implement."""
    unknown = sorted(set(config) - KNOWN)
    if unknown:
        raise ValueError(f"the GLM family implements no key {unknown}")
    for key, want in FIXED.items():
        if key in config and config[key] != want:
            raise ValueError(f"{key}: {config[key]!r}; the equations here "
                             f"are written for {want!r}")
    if int(config.get("qk_head_dim", config["qk_nope_head_dim"])) \
            != int(config["qk_nope_head_dim"]):
        raise ValueError("qk_head_dim is not qk_nope_head_dim + 0")
    if int(config.get("num_nextn_predict_layers", 0)):
        raise ValueError("num_nextn_predict_layers: no multi-token "
                         "prediction layer is built here")
    if int(config["hc_mult"]) < 2:
        raise ValueError("hc_mult: the residual here is several streams")
    first = int(config.get("first_layer", 0))
    n = int(config["num_hidden_layers"])
    dense = int(config["first_k_dense_replace"])
    want = {
        "layer_types": [SPARSE if p % 4 == 3 else KDA
                        for p in range(first, first + n)],
        "mlp_layer_types": ["dense" if p < dense else "sparse"
                            for p in range(first, first + n)],
        "indexer_types": ["full"] * n}
    for key, lists in want.items():
        if list(config[key]) != lists:
            raise ValueError(f"{key} {config[key]} is not the published "
                             f"pattern at layers {first}..{first + n - 1}: "
                             f"{lists}")
    lin = config["linear_attn_config"]
    if set(lin) - {"num_heads", "gate_lower_bound", "head_dim",
                   "short_conv_kernel_size", "kda_layers",
                   "full_attn_layers"}:
        raise ValueError(f"linear_attn_config: {sorted(lin)}")


def layer_types(config: Dict):
    """Each held layer's mixer in the builder's words."""
    return tuple("sparse_latent" if t == SPARSE else "kda"
                 for t in config["layer_types"])


def program_config(config: Dict, max_positions: int):
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.models.latent_moe import LatentMoEConfig

    check(config)
    pub = config.get("published") or {}
    lin = config["linear_attn_config"]
    held = int(config["n_routed_experts"])
    idim = int(config["index_head_dim"])
    return LatentMoEConfig(
        vocab_size=int(config["vocab_size"]),
        max_positions=int(max_positions),
        hidden_size=int(config["hidden_size"]),
        num_layers=int(config["num_hidden_layers"]),
        num_heads=int(config["num_attention_heads"]),
        q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=0, v_head_dim=int(config["v_head_dim"]),
        rms_eps=float(config["rms_norm_eps"]),
        first_dense=int(config["first_k_dense_replace"]),
        dense_width=int(config["intermediate_size"]),
        expert_width=int(config["moe_intermediate_size"]),
        n_routed=int(pub.get("n_routed_experts", held)),
        experts_per_token=int(config["num_experts_per_tok"]),
        n_group=1, topk_group=1, scoring="sigmoid", norm_topk=True,
        routed_scale=float(config["routed_scaling_factor"]),
        n_shared=1, experts_held=(int(config.get("expert_first", 0)), held),
        selection_bias=True, layer_types=layer_types(config),
        first_layer=int(config.get("first_layer", 0)),
        kda_heads=int(lin["num_heads"]), kda_head_dim=int(lin["head_dim"]),
        kda_conv_taps=int(lin["short_conv_kernel_size"]),
        kda_lower_bound=float(lin["gate_lower_bound"]),
        kda_decay_rank=int(lin["head_dim"]),
        kda_gate_rank=int(lin["head_dim"]),
        indexer=dict(heads=int(config["index_n_heads"]), dim=idim,
                     rope_dim=int(config.get("index_rope_dim",
                                             min(64, idim))),
                     pool=int(config["index_kpool"]),
                     topk=int(config["index_topk"]),
                     theta=float(config.get("index_rope_theta", 10000.0))),
        hc_mult=int(config["hc_mult"]),
        hc_sinkhorn_iters=int(config["hc_sinkhorn_iters"]),
        hc_eps=float(config["hc_eps"]),
        swiglu_limit=(None if config.get("swiglu_limit") is None
                      else float(config["swiglu_limit"])),
        param_dtype=DataType.BFLOAT16, draw_weights=False)


def build(ff, config: Dict, batch: int, seq: int) -> None:
    """Add the model's layers to ``ff`` through ``models/latent_moe.py``."""
    from flexflow_tpu.models.latent_moe import build_latent_moe_lm

    if seq > int(config["max_position_embeddings"]):
        raise ValueError(f"{seq} positions exceed the model's "
                         f"{config['max_position_embeddings']}")
    build_latent_moe_lm(ff, batch, seq, program_config(config, seq))


_KDA = ("wq", "wk", "wv", "wf_a", "wf_b", "wb", "wg_a", "wg_b", "conv",
        "a_log", "dt_bias", "norm", "wo")
_SPARSE = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo",
           "wq_i", "wk_i", "k_norm_i", "k_bias_i", "ww_i")
_MLP = ("gate", "up", "down")
_MIX = ("w", "scale", "bias")


def _dense(config: Dict, i: int) -> bool:
    return config["mlp_layer_types"][i] == "dense"


def to_program(weights: Dict, config: Dict) -> Dict[str, Dict]:
    """Reference weights -> ``{op name: {weight name: array}}`` as
    ``CompiledModel.params`` holds them: the same arrays, renamed."""
    w = weights
    out = {"embed": {"weight": w["embed"]}, "norm_f": {"scale": w["norm_f"]},
           "lm_head": {"kernel": w["lm_head"]}}
    for i, kind in enumerate(layer_types(config)):
        p = f"l{i}."
        for j in (1, 2):
            out[f"block{i}_norm{j}"] = {"scale": w[p + f"norm{j}"]}
            out[f"block{i}_res{j}_pre"] = {k: w[p + f"mix{j}." + k]
                                           for k in _MIX}
        out[f"block{i}_attn"] = {k: w[p + k]
                                 for k in (_KDA if kind == "kda" else _SPARSE)}
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


def _attn_names(config: Dict, kind: str):
    return [f"block{i}_attn"
            for i, k in enumerate(layer_types(config)) if k == kind]


def state_layer_names(config: Dict):
    """The program's KDA ops, in layer order."""
    return _attn_names(config, "kda")


def sparse_layer_names(config: Dict):
    """The program's sparse latent ops, in layer order."""
    return _attn_names(config, "sparse_latent")


# ---- what the readers ask of a family ------------------------------------------
# ``run["family"]`` is this module (``benchmark/run.py``). A function answers
# None where the window holds no such counters.


def index_window(run: Dict):
    """The window's deltas of ``stats()["kv"]["index"]`` (ONE sparse
    layer's steps and chunks) and its decode ``steps``; None from a
    program that does not count them or a window without a step."""
    from benchmark import routed_chunked

    d = routed_chunked._delta(run, "kv", "index")
    f = run["facts"]
    if not d or d.get("rows_live", 0) <= 0:
        return None
    steps = f["stats1"]["decode_steps"] - f["stats0"]["decode_steps"]
    return dict(d, steps=steps) if steps > 0 else None


def _kda_rows_stepped(run: Dict):
    """(slot, layer) KDA states the window's decode steps updated: the
    pool's ``rows_stepped`` counts every op that keeps a row a request, the
    sparse layers' open pools among them; the KDA layers' share of it."""
    from benchmark import state_window

    rows = state_window.rows_stepped(run)
    if rows is None:
        return None
    cfg = run["config"]
    kda = len(state_layer_names(cfg))
    return rows * kda / (kda + len(sparse_layer_names(cfg)))


def decode_step_least_s(run: Dict):
    """``decode_step_roofline``: every matrix read once in bfloat16 (of
    the held experts only the share that got a row: the window's
    ``stats()["moe"]``), every stepped KDA state once in and once out at
    its float32 bytes, the pooled keys of the sparse layer's live rows
    once and the rows it took once (the window's
    ``stats()["kv"]["index"]``), ``counts_glm.decode_bytes_per_step``,
    over the HBM peak."""
    from benchmark import counts_glm, routed_window

    ix, rows = index_window(run), _kda_rows_stepped(run)
    hit = routed_window.expert_hit_share(run)
    if ix is None or rows is None or hit is None:
        return None
    return (counts_glm.decode_bytes_per_step(
        run["config"], ix["rows_live"] / ix["steps"],
        ix["rows_read"] / ix["steps"], rows / ix["steps"], hit)
        / run["peaks"]["hbm_bytes_per_s"])


def cache_bytes(run: Dict):
    """``state_bytes_share``: ``(state, rest)``, the KDA states the
    window's steps updated, in and out, beside the pooled keys and taken
    rows its sparse layer read (``counts_glm``)."""
    from benchmark import counts_glm

    ix, rows = index_window(run), _kda_rows_stepped(run)
    if ix is None or rows is None:
        return None
    cfg = run["config"]
    return (rows * 2 * counts_glm.state_bytes(cfg),
            len(sparse_layer_names(cfg)) * counts_glm.sparse_step_bytes(
                cfg, ix["rows_live"], ix["rows_read"]))


def state_step_least_s(run: Dict):
    """``kda_state_roofline``: the stepped KDA states' bytes once in and
    once out over the HBM peak, a step."""
    from benchmark import counts_glm

    ix, rows = index_window(run), _kda_rows_stepped(run)
    if ix is None or rows is None:
        return None
    return counts_glm.state_step_least_s(run["config"], rows / ix["steps"],
                                         run["peaks"])


def sparse_step_least_s(run: Dict):
    """``sparse_latent_roofline``: the pooled keys of the live rows read
    once and the taken rows read once, a step of ONE sparse layer times
    the sparse layers, over the HBM peak."""
    from benchmark import counts_glm

    ix = index_window(run)
    if ix is None:
        return None
    cfg = run["config"]
    return (len(sparse_layer_names(cfg)) * counts_glm.sparse_step_bytes(
        cfg, ix["rows_live"] / ix["steps"], ix["rows_read"] / ix["steps"])
        / run["peaks"]["hbm_bytes_per_s"])


def chunk_least_s(run: Dict):
    """``prefill_chunk_mfu``: every fixed matrix once a live token, the
    held experts' matrices once a pair the routing named among them, the
    KDA rule a token, the indexer's scores of the pools before each query
    and the attention over the rows each query TOOK (not the rows the
    first form walks), ``counts_glm.chunk_flops`` over the window's
    chunks, over the bfloat16 peak."""
    from benchmark import counts_glm, routed_chunked

    n = routed_chunked.chunks(run)
    d = routed_chunked._delta(run, "kv", "index")
    if n is None or not d or d.get("rows_taken", 0) <= 0:
        return None
    pool = int(run["config"]["index_kpool"])
    return (counts_glm.chunk_flops(
        run["config"], n["tokens"], n["keys_full"] / pool, d["rows_taken"],
        n["pairs_held"])
        / n["chunks"] / run["peaks"]["bf16_flops_per_s"])
