"""Decoder-only causal LM with latent attention and routed experts.

No reference analog. The block of the DeepSeek-V2/V3 line of models:
token embedding (positions are rotary, inside the attention), pre-norm
blocks ``h = x + latent_attention(rms_norm(x)); y = h + ffn(rms_norm(h))``
where ``ffn`` is a dense gated MLP in the first ``first_dense`` layers
and, after them, routed experts plus one shared gated MLP; a final
RMSNorm and an untied vocabulary head.

One builder serves the whole model and one holder's share of it:
``experts_held = (first, count)`` makes every expert layer route over all
``n_routed`` experts and compute only the held ones (the shared expert,
attention, router and dense layers are whole on every holder), and
``vocab_size`` is whatever slice of the vocabulary the holder keeps.

A layer takes its mixer and its feed-forward by its PUBLISHED index
``first_layer + i`` (a pipeline stage holds a run of layers from
``first_layer``): ``layer_types`` names each held layer's mixer, ``"latent"``
(every layer, by default) or ``"kda"`` (Kimi Delta Attention,
``ops/gated_delta.py``: the hybrids of the Ling 3.0 and Kimi Linear line,
some linear layers to one latent layer) or ``"sparse_latent"`` (latent
attention under the learned ``indexer``, DeepSeek-V3.2's sparse
attention), and the first ``first_dense`` published layers have the dense
MLP. With ``hc_mult`` n > 1 the residual is n streams
(``ops/stream_mix.py``): the embedding spread into them, every mixer and
every feed-forward read from them and written back under its own mix,
their sum before the final norm. ``swiglu_limit`` clamps every gated MLP,
dense, shared and routed.

Built on the builder API, so the graph compiles, is priced by the search
and the simulator, and drives ``serving.GenerationInstance`` (a paged
latent cache, one row a token). ``param_dtype`` is the dtype the graph's
weights are STORED in: float32 masters by default, ``BFLOAT16`` for an
inference graph that holds its matrices once. With ``draw_weights=False``
the weights are declared and not drawn (``DeclaredInitializer``), for a
graph that is loaded before it runs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

from ..ffconst import DataType
from ..runtime.initializer import DeclaredInitializer


@dataclasses.dataclass
class LatentMoEConfig:
    vocab_size: int = 32000
    max_positions: int = 4096
    hidden_size: int = 512
    num_layers: int = 4
    num_heads: int = 8
    q_lora_rank: Optional[int] = 192   # None: queries projected in one step
    kv_lora_rank: int = 64
    qk_nope_head_dim: int = 32
    qk_rope_head_dim: int = 16
    v_head_dim: int = 32
    rope_theta: float = 10000.0
    rope_scaling: Optional[Dict[str, Any]] = None
    rms_eps: float = 1e-6
    first_dense: int = 1
    dense_width: int = 1536
    expert_width: int = 256
    n_routed: int = 16
    experts_per_token: int = 2
    n_group: int = 1
    topk_group: int = 1
    scoring: str = "sigmoid"
    norm_topk: bool = True
    routed_scale: float = 1.0
    n_shared: int = 1
    experts_held: Optional[Tuple[int, int]] = None
    selection_bias: bool = False       # the experts are chosen by s + b
    # each held layer's mixer, "latent" or "kda"; None: every layer latent
    layer_types: Optional[Tuple[str, ...]] = None
    first_layer: int = 0               # the published index of layer 0
    output_gate: Optional[str] = None  # "head": one sigmoid gate a head
    rope_interleaved: bool = False
    kda_head_dim: int = 128            # keys and values of a KDA head
    kda_conv_taps: int = 4
    kda_lower_bound: float = -5.0
    kda_heads: Optional[int] = None    # None: ``num_heads``
    kda_decay_rank: Optional[int] = None   # None: the decay full-rank
    kda_gate_rank: Optional[int] = None    # None: one output gate a head
    # a "sparse_latent" layer's indexer: heads, dim, rope_dim, pool, topk
    indexer: Optional[Dict[str, Any]] = None
    hc_mult: int = 1                   # residual streams
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    swiglu_limit: Optional[float] = None
    param_dtype: DataType = DataType.FLOAT
    draw_weights: bool = True


def build_latent_moe_lm(ff, batch_size: int, seq_length: int,
                        cfg: LatentMoEConfig = LatentMoEConfig()):
    """Returns (tokens, positions, logits); ``logits`` (B, S, vocab)."""
    init = None if cfg.draw_weights else DeclaredInitializer()
    tokens = ff.create_tensor((batch_size, seq_length), DataType.INT32,
                              name="tokens")
    positions = ff.create_tensor((batch_size, seq_length), DataType.INT32,
                                 name="positions")
    h = ff.embedding(tokens, cfg.vocab_size, cfg.hidden_size,
                     dtype=cfg.param_dtype, kernel_initializer=init,
                     name="embed")
    # (absent where the selection has no bias: an older graph's
    # attributes are what they were)
    bias = (dict(selection_bias=True, bias_initializer=init)
            if cfg.selection_bias else {})
    types = cfg.layer_types or ("latent",) * cfg.num_layers
    if (len(types) != cfg.num_layers
            or set(types) - {"latent", "kda", "sparse_latent"}):
        raise ValueError(f"layer_types {types} for {cfg.num_layers} layers "
                         f"of 'latent', 'kda' or 'sparse_latent'")
    if "sparse_latent" in types and not cfg.indexer:
        raise ValueError("a 'sparse_latent' layer needs the indexer")
    # (each stated only where it departs: an older graph's attributes are
    # what they were)
    clamp = ({} if cfg.swiglu_limit is None
             else dict(limit=cfg.swiglu_limit))
    kda = dict(decay_rank=cfg.kda_decay_rank) if cfg.kda_decay_rank else {}
    if cfg.kda_gate_rank:
        kda.update(output_gate="channel", gate_rank=cfg.kda_gate_rank)
    n = cfg.hc_mult

    def sublayer(h, i, j, make):
        """Layer ``i``'s sublayer ``j`` (1 the mixer, 2 the feed-forward),
        ``make(norm(read h))``, written back: over the plain residual, or
        over the streams under the sublayer's own mix."""
        name = f"block{i}_res{j}"
        if n == 1:
            u = h
        else:
            u, coefs = ff.stream_mix_pre(
                h, n, sinkhorn_iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
                norm_eps=cfg.rms_eps, kernel_initializer=init,
                scale_initializer=init, bias_initializer=init,
                name=f"{name}_pre")
        y = make(ff.rms_norm(u, eps=cfg.rms_eps, kernel_initializer=init,
                             name=f"block{i}_norm{j}"))
        if n == 1:
            return ff.add(h, y, name=name)
        return ff.stream_mix_post(h, y, coefs, n, name=name)

    if n > 1:
        h = ff.stream_spread(h, n, name="streams")
    for i in range(cfg.num_layers):
        def mixer(n1, i=i):
            if types[i] == "kda":
                return ff.kimi_delta_attention(
                    n1, num_heads=cfg.kda_heads or cfg.num_heads,
                    key_dim=cfg.kda_head_dim,
                    value_dim=cfg.kda_head_dim, conv_taps=cfg.kda_conv_taps,
                    lower_bound=cfg.kda_lower_bound, eps=cfg.rms_eps,
                    kernel_initializer=init, gain_initializer=init,
                    gate_initializer=init, **kda, name=f"block{i}_attn")
            sparse = (dict(indexer=cfg.indexer, bias_initializer=init)
                      if types[i] == "sparse_latent" else {})
            return ff.latent_attention(
                n1, positions, num_heads=cfg.num_heads,
                q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim,
                v_head_dim=cfg.v_head_dim, max_positions=cfg.max_positions,
                rope_theta=cfg.rope_theta, rope_scaling=cfg.rope_scaling,
                eps=cfg.rms_eps, output_gate=cfg.output_gate,
                rope_interleaved=cfg.rope_interleaved,
                kernel_initializer=init, gain_initializer=init, **sparse,
                name=f"block{i}_attn")

        def ffn(n2, i=i):
            if cfg.first_layer + i < cfg.first_dense:
                return ff.gated_mlp(n2, cfg.dense_width,
                                    kernel_initializer=init, **clamp,
                                    name=f"block{i}_mlp")
            m = ff.routed_experts(
                n2, n_routed=cfg.n_routed,
                experts_per_token=cfg.experts_per_token,
                width=cfg.expert_width, n_group=cfg.n_group,
                topk_group=cfg.topk_group, scoring=cfg.scoring,
                norm_topk=cfg.norm_topk, routed_scale=cfg.routed_scale,
                experts_held=cfg.experts_held, kernel_initializer=init,
                **bias, **clamp, name=f"block{i}_experts")
            if cfg.n_shared:
                shared = ff.gated_mlp(
                    n2, cfg.n_shared * cfg.expert_width,
                    kernel_initializer=init, **clamp,
                    name=f"block{i}_shared")
                m = ff.add(m, shared, name=f"block{i}_ffn")
            return m

        h = sublayer(h, i, 1, mixer)
        h = sublayer(h, i, 2, ffn)
    if n > 1:
        h = ff.stream_sum(h, n, name="streams_sum")
    h = ff.rms_norm(h, eps=cfg.rms_eps, kernel_initializer=init,
                    name="norm_f")
    logits = ff.dense(h, cfg.vocab_size, use_bias=False,
                      kernel_initializer=init, name="lm_head")
    return tokens, positions, logits
