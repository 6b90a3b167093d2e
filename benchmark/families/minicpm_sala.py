"""The MiniCPM-SALA family: how a configuration file becomes the program's
``FFModel`` graph (``flexflow_tpu/models/sparse_hybrid.py``), and how the
reference's weights (``benchmark/reference/minicpm_sala.py``) become the
program's parameter tree.

The graph is built for inference: its matrices are stored in bfloat16,
once, and declared rather than drawn (the benchmark loads the seed's
weights before anything runs). ``to_program`` hands the program the
reference's OWN arrays — same dtype, same layouts, no reshape — so the
chip holds one copy of the weights while both are alive.

A configuration may be one stage of a pipeline: ``num_hidden_layers`` and
``mixer_types`` are then the stage's, ``first_layer`` the published index
of its first layer (``published`` holds the model's depth and types, which
the residual scale and the decay read), with the embedding and the head on
it.
"""

from __future__ import annotations

from typing import Dict, List

REFERENCE = "minicpm_sala"
SPARSE, LINEAR = "minicpm4", "lightning-attn"
SELECTION_KEYS = {"kernel": "kernel_size", "stride": "kernel_stride",
                  "block": "block_size", "window": "window_size",
                  "dense_len": "dense_len", "init_blocks": "init_blocks",
                  "topk": "topk"}


def program_config(config: Dict):
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.models.sparse_hybrid import SparseHybridConfig

    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("the gated MLP here is SiLU's")
    if config.get("attention_bias") or config.get("attn_use_rope"):
        raise ValueError("the sparse layers here have no biases and take no "
                         "rotary embedding")
    if config.get("tie_word_embeddings"):
        raise ValueError("the head here is a matrix of its own")
    for key in ("qk_norm", "lightning_use_rope", "use_output_norm",
                "use_output_gate", "attn_use_output_gate"):
        if not config.get(key, True):
            raise ValueError(f"{key} false is not built here")
    if int(config["lightning_nkv"]) != int(config["lightning_nh"]):
        raise ValueError("linear layers with fewer key heads than heads are "
                         "not built here")
    types = tuple(config["mixer_types"])
    if len(types) != int(config["num_hidden_layers"]):
        raise ValueError(f"{len(types)} mixer_types for "
                         f"{config['num_hidden_layers']} layers")
    published = config.get("published") or {}
    sparse = config["sparse_config"]
    return SparseHybridConfig(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]), mixer_types=types,
        depth=int(published.get("num_hidden_layers", len(types))),
        layer_offset=int(config.get("first_layer", 0)),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        selection={k: int(sparse[v]) for k, v in SELECTION_KEYS.items()},
        linear_heads=int(config["lightning_nh"]),
        linear_head_dim=int(config["lightning_head_dim"]),
        rope_theta=float(config.get("rope_theta", 10000.0)),
        mlp_width=int(config["intermediate_size"]),
        rms_eps=float(config.get("rms_norm_eps", 1e-6)),
        scale_emb=float(config["scale_emb"]),
        scale_depth=float(config["scale_depth"]),
        dim_model_base=int(config["dim_model_base"]),
        param_dtype=DataType.BFLOAT16, draw_weights=False)


def build(ff, config: Dict, batch: int, seq: int) -> None:
    """Add the model's layers to ``ff`` through ``models/sparse_hybrid.py``."""
    from flexflow_tpu.models.sparse_hybrid import build_sparse_hybrid_lm

    if seq > int(config["max_position_embeddings"]):
        raise ValueError(f"{seq} positions exceed the model's "
                         f"{config['max_position_embeddings']}")
    build_sparse_hybrid_lm(ff, batch, seq, program_config(config))


def sparse_layer_names(config: Dict) -> List[str]:
    """The program's ops that select blocks, in layer order."""
    return [f"block{i}_mixer" for i, kind in enumerate(config["mixer_types"])
            if kind == SPARSE]


def to_program(weights: Dict, config: Dict) -> Dict[str, Dict]:
    """Reference weights -> ``{op name: {weight name: array}}`` as
    ``CompiledModel.params`` holds them: the same arrays, renamed."""
    w = weights
    out = {"embed": {"weight": w["embed"]}, "norm_f": {"scale": w["norm_f"]},
           "lm_head": {"kernel": w["lm_head"]}}
    for i, kind in enumerate(config["mixer_types"]):
        p = f"l{i}."
        names = ("wq", "wk", "wv", "wg", "wo", "q_norm", "k_norm") + (
            ("o_norm",) if kind == LINEAR else ())
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
    state of every active slot and linear layer once in and once out in
    float32 (the window's ``rows_stepped``), the blocks the sparse layers'
    steps selected (``selected.blocks_read``) and every live request's
    pooled keys once (counted low from ``selected.blocks_live``),
    ``counts_sala.decode_bytes_per_step``, over the HBM peak."""
    from benchmark import counts_sala, selected_window

    step = selected_window.per_step(run)
    if step is None:
        return None
    return (counts_sala.decode_bytes_per_step(
        run["config"], step["state_rows"], step["selected"],
        step["live_tokens"]) / run["peaks"]["hbm_bytes_per_s"])


def cache_bytes(run: Dict):
    """``state_bytes_share``: ``(state, rest)``, the window's
    ``rows_stepped`` times a state's float32 bytes, in and out, beside the
    selected blocks' keys and values over the sparse layers and the live
    requests' pooled keys (``counts_sala``)."""
    from benchmark import counts_sala, selected_window

    step = selected_window.per_step(run)
    if step is None:
        return None
    cfg = run["config"]
    state = step["state_rows"] * 2 * counts_sala.state_bytes(cfg)
    rest = (counts_sala.decode_bytes_per_step(
        cfg, 0, step["selected"], step["live_tokens"])
        - counts_sala.matrix_params(cfg) * 2)
    return state, rest


def chunk_least_s(run: Dict):
    """``prefill_chunk_mfu``: every layer's matrices once a live token
    (the window's ``prefill_tokens`` over its ``prefill_chunks``: a
    prompt's last chunk is padded, and padding counts for nothing) and
    the linear layers' state products, the sparse layers' attention left
    out (the counters prove no context), ``counts_sala.chunk_flops``,
    over the bfloat16 peak."""
    from benchmark import counts_sala, selected_window

    n = selected_window.chunks(run)
    if n is None:
        return None
    return (counts_sala.chunk_flops(run["config"], n["tokens"] / n["chunks"])
            / run["peaks"]["bf16_flops_per_s"])
