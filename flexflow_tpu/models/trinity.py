"""Decoder-only causal LM of windowed and full attention layers, mixed
by a list, with gated grouped-head attention and routed experts.

No reference analog. The block of the Arcee Trinity (``afmoe``) line,
with four norms a layer (sandwich norms): ``h = x + rms(attention(rms(x)));
y = h + rms(ffn(rms(h)))``. ``layer_types`` names each layer's attention:
``"sliding_attention"`` keeps and sees the last ``window`` tokens and
rotates q and k by the positions, ``"full_attention"`` sees everything
and takes no positions at all. Both are grouped heads of ``head_dim``
(``heads x head_dim`` need not be the hidden size) with an RMSNorm over
each head of q and k and a sigmoid gate on the attended values
(ops/attention.py ``MultiHeadAttention``: ``head_dim``, ``window``,
``rotary``, ``qk_norm="head"``, ``gate``). ``ffn`` is a dense gated MLP
in the first ``num_dense`` layers and after them routed experts
(sigmoid scores, a selection bias, ``routed_scale``) beside one shared
gated MLP. The embedding is scaled by ``sqrt(hidden)``; a final RMSNorm
and an untied vocabulary head.

One builder serves the whole model and one holder's share of it
(``experts_held``, ``vocab_size``), with ``param_dtype`` and
``draw_weights`` as in ``models/latent_moe.py``. Built on the builder
API, so the graph compiles, is priced by the search and the simulator,
and drives ``serving.GenerationInstance``: a full layer keeps a (k, v)
pair a token in the paged pool, a windowed layer a ring of ``window``
rows a request beside it (serving/cache_entry.py ``WindowEntry``).

The same builder takes the Xiaomi MiMo-V2 (``mimo_v2``) line, which is
this skeleton (layers of two kinds by a list, a dense MLP in the first
layers and sigmoid experts with a selection bias after them) with other
answers to what a configuration can say, each a field whose default is
Trinity's: two norms a layer and not four (``sandwich_norms``), no norm
over a head and no gate (``qk_norm``, ``gate``), key-value heads a kind
of layer (``num_kv_heads_sliding``), values narrower than keys
(``v_head_dim``), rotary over the first ``rotary_dim`` numbers of a head
in BOTH kinds with a base each (``rope_theta_full``), a learned sink a
head in the kinds ``sink_layers`` names, values scaled
(``value_scale``), no shared expert (``n_shared`` 0) and an embedding as
it is (``scale_embedding``). One builder and not a sibling file: what
differs is configuration, and ROADMAP D17 counts a file a configuration
as debt.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

from ..ffconst import DataType
from ..runtime.initializer import DeclaredInitializer

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass
class TrinityConfig:
    vocab_size: int = 32000
    hidden_size: int = 512
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 128
    window: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    num_dense: int = 1
    dense_width: int = 2048
    expert_width: int = 512
    n_routed: int = 16
    experts_per_token: int = 4
    routed_scale: float = 1.0
    norm_topk: bool = True
    n_shared: int = 1
    experts_held: Optional[Tuple[int, int]] = None
    scale_embedding: bool = True
    # what the MiMo line answers otherwise (the module's docstring)
    sandwich_norms: bool = True
    qk_norm: bool = True
    gate: bool = True
    num_kv_heads_sliding: Optional[int] = None   # None: ``num_kv_heads``
    v_head_dim: Optional[int] = None             # None: ``head_dim``
    rotary_dim: Optional[int] = None             # None: the whole head
    rope_theta_full: Optional[float] = None      # None: no positions there
    sink_layers: Tuple[str, ...] = ()
    value_scale: Optional[float] = None
    param_dtype: DataType = DataType.FLOAT
    draw_weights: bool = True


def build_trinity_lm(ff, batch_size: int, seq_length: int,
                     cfg: TrinityConfig = TrinityConfig()):
    """Returns (tokens, positions, logits); ``logits`` (B, S, vocab)."""
    init = None if cfg.draw_weights else DeclaredInitializer()
    tokens = ff.create_tensor((batch_size, seq_length), DataType.INT32,
                              name="tokens")
    positions = ff.create_tensor((batch_size, seq_length), DataType.INT32,
                                 name="positions")
    h = ff.embedding(tokens, cfg.vocab_size, cfg.hidden_size,
                     dtype=cfg.param_dtype, kernel_initializer=init,
                     name="embed")
    if cfg.scale_embedding:
        h = ff.scalar_multiply(h, math.sqrt(cfg.hidden_size),
                               name="embed_scale")
    for i, kind in enumerate(cfg.layer_types):
        if kind not in (SLIDING, FULL):
            raise ValueError(f"layer {i}: {kind!r} is neither {SLIDING!r} "
                             f"nor {FULL!r}")
        norm = lambda x, what: ff.rms_norm(  # noqa: E731
            x, eps=cfg.rms_eps, kernel_initializer=init,
            name=f"block{i}_{what}")
        u = norm(h, "norm_in")
        sliding = kind == SLIDING
        theta = cfg.rope_theta if sliding else cfg.rope_theta_full
        attn = ff.multihead_attention(
            u, u, u, cfg.hidden_size, cfg.num_heads, bias=False, causal=True,
            num_kv_heads=(cfg.num_kv_heads_sliding if sliding
                          and cfg.num_kv_heads_sliding else cfg.num_kv_heads),
            head_dim=cfg.head_dim,
            qk_norm="head" if cfg.qk_norm else False, norm_eps=cfg.rms_eps,
            gate=cfg.gate,
            window=cfg.window if sliding else None,
            rotary=theta, positions=positions if theta else None,
            v_head_dim=cfg.v_head_dim, rotary_dim=cfg.rotary_dim,
            sinks=kind in cfg.sink_layers, sink_initializer=init,
            value_scale=cfg.value_scale,
            kernel_initializer=init, gain_initializer=init,
            name=f"block{i}_attn")
        if cfg.sandwich_norms:
            attn = norm(attn, "norm_post_attn")
        h = ff.add(h, attn, name=f"block{i}_res1")
        m = norm(h, "norm_pre_mlp")
        if i < cfg.num_dense:
            f = ff.gated_mlp(m, cfg.dense_width, kernel_initializer=init,
                             name=f"block{i}_mlp")
        else:
            f = ff.routed_experts(
                m, n_routed=cfg.n_routed,
                experts_per_token=cfg.experts_per_token,
                width=cfg.expert_width, scoring="sigmoid",
                selection_bias=True, norm_topk=cfg.norm_topk,
                routed_scale=cfg.routed_scale,
                experts_held=cfg.experts_held, kernel_initializer=init,
                bias_initializer=init, name=f"block{i}_experts")
            if cfg.n_shared:
                shared = ff.gated_mlp(
                    m, cfg.n_shared * cfg.expert_width,
                    kernel_initializer=init, name=f"block{i}_shared")
                f = ff.add(f, shared, name=f"block{i}_ffn")
        if cfg.sandwich_norms:
            f = norm(f, "norm_post_mlp")
        h = ff.add(h, f, name=f"block{i}_res2")
    h = ff.rms_norm(h, eps=cfg.rms_eps, kernel_initializer=init,
                    name="norm_f")
    logits = ff.dense(h, cfg.vocab_size, use_bias=False,
                      kernel_initializer=init, name="lm_head")
    return tokens, positions, logits
