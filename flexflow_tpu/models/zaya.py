"""Decoder-only causal LM of the Zyphra ZAYA1 line: every layer a
compressed-convolutional-attention sublayer and a routed-experts
sublayer, each behind a learned residual scaling.

No reference analog. A layer, ``x`` the residual stream, ``rms`` an
RMSNorm with a gain, ``a .. d`` learned vectors a sublayer::

    x = (a1 * x + b1) + (c1 * CCA(rms(x)) + d1)
    x = (a2 * x + b2) + (c2 * MoE(rms(x), r_prev) + d2)

``CCA`` (ops/attention.py ``CompressedConvAttention``) attends grouped
heads whose queries and keys passed two short causal convolutions, whose
values take half of each head from the token before, and whose positions
rotate ``partial_rotary`` of a head. ``MoE`` (ops/moe_ops.py
``RoutedExperts``, ``router="mlp"``) takes ONE expert a token by the
softmax of an MLP over a router state of ``router_width`` numbers, which
every layer but the model's first adds the state of the layer before to
(``r_prev``: a second stream down the layer stack beside ``x``); a
balancing bias stands in the choice only, the expert's output is weighted
by its own unnormalised probability. A final RMSNorm, and a head that IS
the embedding's table.

One builder serves the whole model and a pipeline stage that starts at
``first_layer`` (past 0 the graph takes the router state the stage before
hands over as a third input, ``router_state``; serving binds tokens and
positions only, so it serves stage 0), with ``param_dtype`` and
``draw_weights`` as in ``models/latent_moe.py``. Built on the builder API, so the graph
compiles, is priced by the search and the simulator, trains through
``fit`` and drives ``serving.GenerationInstance``: a layer keeps a (k, v)
pair a token in the paged pool and, a request, its convolutions' tail and
the last token's half value (serving/cache_entry.py ``CcaEntry``); the
router state is a value of the step and is never cached.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from ..ffconst import DataType
from ..runtime.initializer import DeclaredInitializer


@dataclasses.dataclass
class ZayaConfig:
    vocab_size: int = 32000
    hidden_size: int = 512
    num_layers: int = 4
    first_layer: int = 0            # the model's layer this graph starts at
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 128
    conv_taps: Tuple[int, int] = (2, 2)
    rope_theta: float = 5000000.0
    partial_rotary: float = 0.5
    rms_eps: float = 1e-5
    n_routed: int = 16
    experts_per_token: int = 1
    expert_width: int = 2048
    router_width: int = 256
    experts_held: Optional[Tuple[int, int]] = None
    param_dtype: DataType = DataType.FLOAT
    draw_weights: bool = True


def build_zaya_lm(ff, batch_size: int, seq_length: int,
                  cfg: ZayaConfig = ZayaConfig()):
    """Returns (tokens, positions, logits); ``logits`` (B, S, vocab)."""
    init = None if cfg.draw_weights else DeclaredInitializer()
    inits = dict(kernel_initializer=init, gain_initializer=init,
                 bias_initializer=init)
    tokens = ff.create_tensor((batch_size, seq_length), DataType.INT32,
                              name="tokens")
    positions = ff.create_tensor((batch_size, seq_length), DataType.INT32,
                                 name="positions")
    h = ff.embedding(tokens, cfg.vocab_size, cfg.hidden_size,
                     dtype=cfg.param_dtype, kernel_initializer=init,
                     name="embed")

    def merged(x, y, name):
        """``(a * x + b) + (c * y + d)``."""
        return ff.add(
            ff.scale_shift(x, init, init, name=f"{name}_stream"),
            ff.scale_shift(y, init, init, name=f"{name}_branch"), name=name)

    # the model's first layer has no router state before it
    state = ff.create_tensor(
        (batch_size, seq_length, cfg.router_width), DataType.FLOAT,
        name="router_state") if cfg.first_layer else None
    for i in range(cfg.num_layers):
        u = ff.rms_norm(h, eps=cfg.rms_eps, kernel_initializer=init,
                        name=f"block{i}_norm_attn")
        attn = ff.compressed_conv_attention(
            u, positions, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            taps0=cfg.conv_taps[0], taps1=cfg.conv_taps[1],
            rotary=cfg.rope_theta,
            rotary_dim=int(cfg.head_dim * cfg.partial_rotary), **inits,
            name=f"block{i}_attn")
        h = merged(h, attn, f"block{i}_res1")
        m = ff.rms_norm(h, eps=cfg.rms_eps, kernel_initializer=init,
                        name=f"block{i}_norm_moe")
        f, state = ff.routed_experts(
            m, n_routed=cfg.n_routed,
            experts_per_token=cfg.experts_per_token, width=cfg.expert_width,
            scoring="softmax", norm_topk=False, selection_bias=True,
            experts_held=cfg.experts_held, router="mlp",
            router_width=cfg.router_width, router_eps=cfg.rms_eps,
            router_state=state, **inits,
            name=f"block{i}_experts")
        h = merged(h, f, f"block{i}_res2")
    h = ff.rms_norm(h, eps=cfg.rms_eps, kernel_initializer=init,
                    name="norm_f")
    logits = ff.dense(h, cfg.vocab_size, use_bias=False, tied_to="embed",
                      name="lm_head")
    return tokens, positions, logits
