"""Decoder-only causal LM whose every block is a mixer AND a gated SiLU
MLP, each behind a pre-norm and a SCALED residual, with the mixers named
by a ``layer_types`` list: ``"mamba"`` a state-space mixer (ops/mamba2.py:
a state of fixed size a sequence), ``"attention"`` causal attention with
grouped heads, no biases, no positional encoding (the state-space layers
carry the order) and a softmax scale of the model's own.

No reference analog. The block of the Granite 4.0-H line::

    h = embedding[tokens] * embedding_multiplier
    h = h + residual_multiplier * mixer(rms_norm(h))
    h = h + residual_multiplier * gated_mlp(rms_norm(h))
    logits = rms_norm(h) embedding^T / logits_scaling

The head is TIED: it has no matrix of its own and multiplies by the
embedding's table transposed (``ff.dense(..., tied_to="embed")``; the
parameter tree holds the table once). A sibling of ``models/hybrid.py``
(post-norm blocks, gated-delta mixers, an untied head) and of
``models/nemotron_h.py`` (one mixer a layer, squared-ReLU experts) and a
case of neither. The multipliers are ``ff.scalar_multiply`` ops beside
the op they scale (XLA fuses each into its neighbour: no pass over ``h``
of their own); one that is 1 adds no op.

``param_dtype`` and ``draw_weights`` as in ``models/latent_moe.py``.
Built on the builder API, so the graph compiles, is priced by the search
and the simulator, and drives ``serving.GenerationInstance``: the
attention layers keep a (k, v) pair a token in the paged pool, the Mamba
layers a state a request beside it, and a prompt may be prefilled in
chunks (``prefill_chunk``): both kinds continue from what the chunk
before left.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from ..ffconst import ActiMode, DataType
from ..runtime.initializer import DeclaredInitializer

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass
class GraniteHybridConfig:
    vocab_size: int = 32000
    hidden_size: int = 512
    layer_types: Tuple[str, ...] = (MAMBA, MAMBA, ATTENTION, MAMBA)
    rms_eps: float = 1e-5
    mlp_width: int = 2048
    # the four numbers the line scales by
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: Optional[float] = None   # None: 1 / sqrt(head)
    logits_scaling: float = 8.0
    # "mamba"
    mamba_heads: int = 16
    mamba_head_dim: int = 64
    state_size: int = 128
    n_groups: int = 1
    conv_taps: int = 4
    chunk_size: int = 256
    # "attention"
    num_heads: int = 8                 # of hidden / heads each
    num_kv_heads: int = 2
    param_dtype: DataType = DataType.FLOAT
    draw_weights: bool = True


def build_granite_hybrid_lm(ff, batch_size: int, seq_length: int,
                            cfg: GraniteHybridConfig = GraniteHybridConfig()):
    """Returns (tokens, logits); ``logits`` (B, S, vocab). The graph has
    no positions input: no layer would read it."""
    init = None if cfg.draw_weights else DeclaredInitializer()

    def times(x, by: float, name: str):
        return x if by == 1.0 else ff.scalar_multiply(x, float(by), name=name)

    tokens = ff.create_tensor((batch_size, seq_length), DataType.INT32,
                              name="tokens")
    h = ff.embedding(tokens, cfg.vocab_size, cfg.hidden_size,
                     dtype=cfg.param_dtype, kernel_initializer=init,
                     name="embed")
    h = times(h, cfg.embedding_multiplier, "embed_scale")
    for i, kind in enumerate(cfg.layer_types):
        u = ff.rms_norm(h, eps=cfg.rms_eps, kernel_initializer=init,
                        name=f"block{i}_norm")
        if kind == MAMBA:
            mixed = ff.mamba2(
                u, num_heads=cfg.mamba_heads, head_dim=cfg.mamba_head_dim,
                state_size=cfg.state_size, n_groups=cfg.n_groups,
                conv_taps=cfg.conv_taps, chunk_size=cfg.chunk_size,
                eps=cfg.rms_eps, kernel_initializer=init,
                gain_initializer=init, gate_initializer=init,
                name=f"block{i}_mixer")
        elif kind == ATTENTION:
            mixed = ff.multihead_attention(
                u, u, u, cfg.hidden_size, cfg.num_heads, bias=False,
                causal=True, num_kv_heads=cfg.num_kv_heads,
                scale=cfg.attention_multiplier, kernel_initializer=init,
                name=f"block{i}_mixer")
        else:
            raise ValueError(f"layer {i}: {kind!r} is neither {MAMBA!r} "
                             f"nor {ATTENTION!r}")
        h = ff.add(h, times(mixed, cfg.residual_multiplier,
                            f"block{i}_mixer_scale"), name=f"block{i}_res")
        v = ff.rms_norm(h, eps=cfg.rms_eps, kernel_initializer=init,
                        name=f"block{i}_mlp_norm")
        mlp = ff.gated_mlp(v, cfg.mlp_width, ActiMode.SILU,
                           kernel_initializer=init, name=f"block{i}_mlp")
        h = ff.add(h, times(mlp, cfg.residual_multiplier,
                            f"block{i}_mlp_scale"), name=f"block{i}_mlp_res")
    h = ff.rms_norm(h, eps=cfg.rms_eps, kernel_initializer=init,
                    name="norm_f")
    logits = ff.dense(h, cfg.vocab_size, use_bias=False, tied_to="embed",
                      name="lm_head")
    logits = times(logits, 1.0 / cfg.logits_scaling, "logits_scale")
    return tokens, logits
