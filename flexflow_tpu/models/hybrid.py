"""Decoder-only causal LM whose layers mix the sequence in different
ways: ``layer_types`` names, layer by layer, ``"linear_attention"`` (a
gated-delta-rule layer, ops/gated_delta.py: a state of fixed size a
sequence) or ``"full_attention"`` (causal multi-head attention without
biases, an RMSNorm over the whole projected q and k, no positional
encoding: the recurrent layers before it carry the order).

No reference analog. The block is the post-norm one of the OLMo 2 line:
``h = x + rms_norm(mixer(x)); y = h + rms_norm(mlp(h))`` with a gated
SiLU MLP; token embedding, the blocks, a final RMSNorm, an untied
vocabulary head.

Built on the builder API, so the graph compiles, is priced by the search
and the simulator, and drives ``serving.GenerationInstance``: the full
layers keep a (k, v) pair a token in the paged pool, the linear layers a
state a request beside it. ``param_dtype`` and ``draw_weights`` as in
``models/latent_moe.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

from ..ffconst import DataType
from ..runtime.initializer import DeclaredInitializer

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass
class HybridLMConfig:
    vocab_size: int = 32000
    hidden_size: int = 512
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL)
    num_heads: int = 8                 # the full layers', of hidden / heads
    linear_heads: int = 8
    linear_key_dim: int = 64
    linear_value_dim: int = 128
    conv_taps: int = 4
    allow_neg_eigval: bool = True
    mlp_width: int = 1536
    rms_eps: float = 1e-6
    param_dtype: DataType = DataType.FLOAT
    draw_weights: bool = True


def build_hybrid_lm(ff, batch_size: int, seq_length: int,
                    cfg: HybridLMConfig = HybridLMConfig()):
    """Returns (tokens, logits); ``logits`` (B, S, vocab). The graph has
    no positions input: no layer would read it."""
    init = None if cfg.draw_weights else DeclaredInitializer()
    tokens = ff.create_tensor((batch_size, seq_length), DataType.INT32,
                              name="tokens")
    h = ff.embedding(tokens, cfg.vocab_size, cfg.hidden_size,
                     dtype=cfg.param_dtype, kernel_initializer=init,
                     name="embed")
    for i, kind in enumerate(cfg.layer_types):
        if kind == LINEAR:
            mixed = ff.gated_delta_net(
                h, num_heads=cfg.linear_heads, key_dim=cfg.linear_key_dim,
                value_dim=cfg.linear_value_dim, conv_taps=cfg.conv_taps,
                allow_neg_eigval=cfg.allow_neg_eigval, eps=cfg.rms_eps,
                kernel_initializer=init, gain_initializer=init,
                gate_initializer=init, name=f"block{i}_mixer")
        elif kind == FULL:
            mixed = ff.multihead_attention(
                h, h, h, cfg.hidden_size, cfg.num_heads, bias=False,
                causal=True, qk_norm=True, norm_eps=cfg.rms_eps,
                kernel_initializer=init, gain_initializer=init,
                name=f"block{i}_mixer")
        else:
            raise ValueError(f"layer {i}: {kind!r} is neither {LINEAR!r} "
                             f"nor {FULL!r}")
        n1 = ff.rms_norm(mixed, eps=cfg.rms_eps, kernel_initializer=init,
                         name=f"block{i}_norm1")
        h = ff.add(h, n1, name=f"block{i}_res1")
        m = ff.gated_mlp(h, cfg.mlp_width, kernel_initializer=init,
                         name=f"block{i}_mlp")
        n2 = ff.rms_norm(m, eps=cfg.rms_eps, kernel_initializer=init,
                         name=f"block{i}_norm2")
        h = ff.add(h, n2, name=f"block{i}_res2")
    h = ff.rms_norm(h, eps=cfg.rms_eps, kernel_initializer=init,
                    name="norm_f")
    logits = ff.dense(h, cfg.vocab_size, use_bias=False,
                      kernel_initializer=init, name="lm_head")
    return tokens, logits
