"""Decoder-only causal LM of the MiniCPM-SALA kind: ``mixer_types``
names, layer by layer, ``"minicpm4"`` (block-sparse attention with
grouped key-value heads, ops/block_sparse_attention.py: past
``dense_len`` positions a query reads the 64 key blocks a score over
mean-pooled keys selects) or ``"lightning-attn"`` (linear attention with
a fixed decay a head, ops/lightning_attention.py: a state of fixed size
a sequence, rotary positions).

No reference analog. The block is the pre-norm one of the MiniCPM line
with its muP scalings: ``h = x + r mixer(rms_norm(x)); y = h + r
mlp(rms_norm(h))`` with ``r = scale_depth / sqrt(depth)`` and a gated
SiLU MLP; the token embedding times ``scale_emb``, the blocks, a final
RMSNorm, and an untied head over ``h / (hidden / dim_model_base)``.

A sibling of ``models/hybrid.py`` and not a branch of it: the block, the
scalings, the positions input and both mixers differ; the two share the
loop over layer kinds and nothing else. ``depth`` and ``layer_offset``
let a builder make one stage of a pipeline: the layers keep the indices
and the residual scale of the whole model.

Built on the builder API, so the graph compiles, is priced by the search
and the simulator, and drives ``serving.GenerationInstance``: a sparse
layer keeps K and V rows a token and a pooled key every ``stride`` tokens
in the paged pool (``SparseEntry``), a linear layer a float32 state a
request beside it (``DecayStateEntry``). Long prompts are prefilled in
chunks that continue from both (``GenerationInstance(...,
prefill_chunk=2048)``): see serving/generation.py. ``param_dtype`` and
``draw_weights`` as in ``models/latent_moe.py``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

from ..ffconst import DataType
from ..runtime.initializer import DeclaredInitializer

SPARSE, LINEAR = "minicpm4", "lightning-attn"


@dataclasses.dataclass
class SparseHybridConfig:
    vocab_size: int = 32000
    hidden_size: int = 512
    mixer_types: Tuple[str, ...] = (LINEAR, SPARSE, LINEAR, LINEAR)
    depth: Optional[int] = None        # the whole model's layers; None: these
    layer_offset: int = 0              # the first layer's index in the model
    num_heads: int = 8                 # the sparse layers' query heads
    num_kv_heads: int = 2
    head_dim: int = 64
    selection: Optional[Dict[str, int]] = None   # Selection's sizes
    linear_heads: int = 8
    linear_head_dim: int = 64
    rope_theta: float = 10000.0
    mlp_width: int = 1536
    rms_eps: float = 1e-6
    scale_emb: float = 1.0
    scale_depth: float = 1.0
    dim_model_base: Optional[int] = None   # None: logits unscaled
    param_dtype: DataType = DataType.FLOAT
    draw_weights: bool = True


def build_sparse_hybrid_lm(ff, batch_size: int, seq_length: int,
                           cfg: SparseHybridConfig = SparseHybridConfig()):
    """Returns (tokens, positions, logits); ``logits`` (B, S, vocab)."""
    init = None if cfg.draw_weights else DeclaredInitializer()
    depth = cfg.depth or len(cfg.mixer_types)
    r = cfg.scale_depth / math.sqrt(depth)
    tokens = ff.create_tensor((batch_size, seq_length), DataType.INT32,
                              name="tokens")
    positions = ff.create_tensor((batch_size, seq_length), DataType.INT32,
                                 name="positions")
    h = ff.embedding(tokens, cfg.vocab_size, cfg.hidden_size,
                     dtype=cfg.param_dtype, kernel_initializer=init,
                     name="embed")
    if cfg.scale_emb != 1.0:
        h = ff.scalar_multiply(h, cfg.scale_emb, name="embed_scale",
                               inplace=False)
    for i, kind in enumerate(cfg.mixer_types):
        u = ff.rms_norm(h, eps=cfg.rms_eps, kernel_initializer=init,
                        name=f"block{i}_norm1")
        if kind == SPARSE:
            mixed = ff.block_sparse_attention(
                u, num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim, selection=cfg.selection,
                eps=cfg.rms_eps, kernel_initializer=init,
                gain_initializer=init, name=f"block{i}_mixer")
        elif kind == LINEAR:
            mixed = ff.lightning_attention(
                u, positions, num_heads=cfg.linear_heads,
                head_dim=cfg.linear_head_dim,
                layer_index=cfg.layer_offset + i, num_layers=depth,
                rope_theta=cfg.rope_theta, eps=cfg.rms_eps,
                kernel_initializer=init, gain_initializer=init,
                name=f"block{i}_mixer")
        else:
            raise ValueError(f"layer {i}: {kind!r} is neither {SPARSE!r} "
                             f"nor {LINEAR!r}")
        h = ff.add(h, ff.scalar_multiply(mixed, r, name=f"block{i}_scale1",
                                         inplace=False),
                   name=f"block{i}_res1")
        m = ff.gated_mlp(ff.rms_norm(h, eps=cfg.rms_eps,
                                     kernel_initializer=init,
                                     name=f"block{i}_norm2"),
                         cfg.mlp_width, kernel_initializer=init,
                         name=f"block{i}_mlp")
        h = ff.add(h, ff.scalar_multiply(m, r, name=f"block{i}_scale2",
                                         inplace=False),
                   name=f"block{i}_res2")
    h = ff.rms_norm(h, eps=cfg.rms_eps, kernel_initializer=init,
                    name="norm_f")
    if cfg.dim_model_base:
        h = ff.scalar_multiply(h, cfg.dim_model_base / cfg.hidden_size,
                               name="logit_scale", inplace=False)
    logits = ff.dense(h, cfg.vocab_size, use_bias=False,
                      kernel_initializer=init, name="lm_head")
    return tokens, positions, logits
