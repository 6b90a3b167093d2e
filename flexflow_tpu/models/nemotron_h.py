"""Decoder-only causal LM whose every layer is ONE pre-norm mixer, named
letter by letter in ``pattern``: ``"M"`` a state-space mixer
(ops/mamba2.py: a state of fixed size a sequence), ``"E"`` a latent
expert layer (ops/moe_ops.py ``RoutedExperts`` with a selection bias,
squared-ReLU experts inside a latent, beside one shared squared-ReLU MLP
at the full width), ``"*"`` causal attention with grouped heads, no
biases and no positional encoding (the ``M`` layers carry the order).

No reference analog. The block of the Nemotron-H line: ``y = x +
mixer(rms_norm(x))``; token embedding, the blocks, a final RMSNorm, an
untied vocabulary head. A sibling of ``models/hybrid.py`` and not a case
of it: that builder's block is a mixer AND a gated MLP, each behind a
post-norm, and its layer list names mixers only; here a feed-forward
part is a layer of its own, the norm stands before the mixer, and the
MLPs are plain.

One builder serves the whole model and one holder's share of it
(``experts_held``, ``vocab_size``), with ``param_dtype`` and
``draw_weights`` as in ``models/latent_moe.py``. Built on the builder
API, so the graph compiles, is priced by the search and the simulator,
and drives ``serving.GenerationInstance``: the ``*`` layers keep a (k, v)
pair a token in the paged pool, the ``M`` layers a state a request beside
it.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from ..ffconst import ActiMode, DataType
from ..runtime.initializer import DeclaredInitializer

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


@dataclasses.dataclass
class NemotronHConfig:
    vocab_size: int = 32000
    hidden_size: int = 512
    pattern: str = "MEM*E"
    rms_eps: float = 1e-5
    # "M"
    mamba_heads: int = 16
    mamba_head_dim: int = 32
    state_size: int = 32
    n_groups: int = 2
    conv_taps: int = 4
    chunk_size: int = 128
    # "*"
    num_heads: int = 8                 # of hidden / heads each
    num_kv_heads: int = 2
    # "E"
    n_routed: int = 16
    experts_per_token: int = 4
    routed_scale: float = 1.0
    norm_topk: bool = True
    latent_size: int = 128
    expert_width: int = 256
    shared_width: int = 512
    experts_held: Optional[Tuple[int, int]] = None
    param_dtype: DataType = DataType.FLOAT
    draw_weights: bool = True


def build_nemotron_h_lm(ff, batch_size: int, seq_length: int,
                        cfg: NemotronHConfig = NemotronHConfig()):
    """Returns (tokens, logits); ``logits`` (B, S, vocab). The graph has
    no positions input: no layer would read it."""
    init = None if cfg.draw_weights else DeclaredInitializer()
    tokens = ff.create_tensor((batch_size, seq_length), DataType.INT32,
                              name="tokens")
    h = ff.embedding(tokens, cfg.vocab_size, cfg.hidden_size,
                     dtype=cfg.param_dtype, kernel_initializer=init,
                     name="embed")
    for i, kind in enumerate(cfg.pattern):
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
                kernel_initializer=init, name=f"block{i}_mixer")
        elif kind == EXPERTS:
            routed = ff.routed_experts(
                u, n_routed=cfg.n_routed,
                experts_per_token=cfg.experts_per_token,
                width=cfg.expert_width, norm_topk=cfg.norm_topk,
                routed_scale=cfg.routed_scale,
                experts_held=cfg.experts_held, selection_bias=True,
                activation="relu2", latent=cfg.latent_size,
                kernel_initializer=init, bias_initializer=init,
                name=f"block{i}_mixer")
            up = ff.dense(u, cfg.shared_width, ActiMode.RELU2,
                          use_bias=False, kernel_initializer=init,
                          name=f"block{i}_shared_up")
            shared = ff.dense(up, cfg.hidden_size, use_bias=False,
                              kernel_initializer=init,
                              name=f"block{i}_shared_down")
            mixed = ff.add(routed, shared, name=f"block{i}_ffn")
        else:
            raise ValueError(f"layer {i}: {kind!r} is none of {MAMBA!r}, "
                             f"{EXPERTS!r}, {ATTENTION!r}")
        h = ff.add(h, mixed, name=f"block{i}_res")
    h = ff.rms_norm(h, eps=cfg.rms_eps, kernel_initializer=init,
                    name="norm_f")
    logits = ff.dense(h, cfg.vocab_size, use_bias=False,
                      kernel_initializer=init, name="lm_head")
    return tokens, logits
