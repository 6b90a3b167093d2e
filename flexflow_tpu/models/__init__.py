"""Model zoo: the reference's example workloads as builder-API definitions
(reference: examples/cpp/* — SURVEY.md §2.8), and the causal LMs serving
drives: ``gpt``, ``latent_moe``, ``hybrid``, ``sparse_hybrid``,
``nemotron_h``, ``trinity`` (``build_trinity_lm``: windowed and full
attention layers by a ``layer_types`` list, gated grouped heads with
rotary positions in the windowed layers only, sandwich norms, leading
dense layers then routed experts beside a shared one; a windowed layer
keeps a ring of ``window`` rows a request in the paged pool:
``serving/cache_entry.py`` ``WindowEntry``) and ``granite_hybrid``
(``build_granite_hybrid_lm``: Mamba-2 and grouped-head attention mixers
by a ``layer_types`` list, every block a mixer and a gated MLP behind
pre-norms and scaled residuals, a softmax scale of the model's own, a
head tied to the embedding; prompts may be prefilled in chunks through
the states: ``SsmStateEntry.chunk``) and ``zaya`` (``build_zaya_lm``:
every layer compressed convolutional attention and top-1 routed experts
under an MLP router whose state runs down the layers, learned residual
scalings, a tied head; a layer keeps a pair a token AND a row a request:
``CcaEntry``)."""

from .mlp import build_mlp
from .alexnet import build_alexnet
from .resnet import build_resnet50
from .resnext import build_resnext50
from .inception import build_inception_v3
from .transformer import build_transformer, build_bert_proxy, TransformerConfig
from .dlrm import build_dlrm, DLRMConfig
from .moe import build_moe_mnist, MoeConfig
from .xdl import build_xdl, XDLConfig
from .candle_uno import build_candle_uno, CandleUnoConfig
from .nmt import build_nmt, NMTConfig
from .gpt import build_gpt, GPTConfig
from .latent_moe import build_latent_moe_lm, LatentMoEConfig
from .hybrid import build_hybrid_lm, HybridLMConfig
from .sparse_hybrid import build_sparse_hybrid_lm, SparseHybridConfig
from .nemotron_h import build_nemotron_h_lm, NemotronHConfig
from .trinity import build_trinity_lm, TrinityConfig
from .granite_hybrid import build_granite_hybrid_lm, GraniteHybridConfig
from .zaya import build_zaya_lm, ZayaConfig


def zoo_smoke_builders():
    """name -> builder(ff, batch_size) for EVERY zoo model, at
    CPU-test-friendly sizes. The single registry the static-analysis
    tooling iterates (tools/pcg_lint.py ``--model all``,
    tests/test_analysis.py's parametrized validator sweep) — adding a
    model here makes it part of the compile-time correctness gate."""

    def mlp(ff, bs):
        build_mlp(ff, bs, in_dim=64, hidden_dims=(128, 128), num_classes=10)

    def alexnet(ff, bs):
        build_alexnet(ff, bs, image_size=64)

    def resnet50(ff, bs):
        build_resnet50(ff, bs, image_size=64)

    def resnext50(ff, bs):
        build_resnext50(ff, bs, image_size=64)

    def inception_v3(ff, bs):
        build_inception_v3(ff, bs, image_size=299)

    def transformer(ff, bs):
        build_transformer(ff, bs, TransformerConfig(
            hidden_size=32, num_heads=4, num_layers=2, sequence_length=16))

    def dlrm(ff, bs):
        build_dlrm(ff, bs, DLRMConfig(embedding_size=[1000] * 4))

    def moe(ff, bs):
        build_moe_mnist(ff, bs, MoeConfig(
            input_dim=16, num_exp=4, num_select=2, expert_hidden_size=32))

    def xdl(ff, bs):
        build_xdl(ff, bs, XDLConfig(embedding_size=[1000] * 4))

    def candle_uno(ff, bs):
        build_candle_uno(ff, bs, CandleUnoConfig(
            dense_layers=[64] * 2, dense_feature_layers=[64] * 2))

    def nmt(ff, bs):
        build_nmt(ff, bs, NMTConfig(
            src_vocab_size=200, tgt_vocab_size=200, embed_dim=32,
            hidden_size=32, num_layers=1, src_length=8, tgt_length=8))

    def gpt(ff, bs):
        build_gpt(ff, bs, 16, GPTConfig(
            vocab_size=128, max_positions=64, hidden_size=32,
            num_heads=4, num_layers=2))

    def latent_moe(ff, bs):
        build_latent_moe_lm(ff, bs, 16, LatentMoEConfig(
            vocab_size=128, max_positions=64, hidden_size=32, num_layers=2,
            num_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            rope_scaling={"type": "yarn", "factor": 4,
                          "original_max_position_embeddings": 16,
                          "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                          "mscale_all_dim": 1},
            first_dense=1, dense_width=64, expert_width=16, n_routed=8,
            experts_per_token=2, n_group=4, topk_group=2,
            routed_scale=2.5))

    def hybrid(ff, bs):
        build_hybrid_lm(ff, bs, 16, HybridLMConfig(
            vocab_size=128, hidden_size=32, num_heads=4, linear_heads=4,
            linear_key_dim=8, linear_value_dim=16, mlp_width=64))

    def sparse_hybrid(ff, bs):
        build_sparse_hybrid_lm(ff, bs, 32, SparseHybridConfig(
            vocab_size=128, hidden_size=32, num_heads=4, num_kv_heads=2,
            head_dim=8, linear_heads=4, linear_head_dim=8, mlp_width=64,
            selection=dict(kernel=4, stride=2, block=4, window=4,
                           dense_len=16, init_blocks=1, topk=3)))

    def nemotron_h(ff, bs):
        build_nemotron_h_lm(ff, bs, 16, NemotronHConfig(
            vocab_size=128, hidden_size=32, pattern="EM*", mamba_heads=4,
            mamba_head_dim=8, state_size=8, n_groups=2, chunk_size=8,
            num_heads=4, num_kv_heads=2, n_routed=8, experts_per_token=2,
            routed_scale=2.0, latent_size=16, expert_width=24,
            shared_width=48))

    def trinity(ff, bs):
        build_trinity_lm(ff, bs, 32, TrinityConfig(
            vocab_size=128, hidden_size=32, num_heads=4, num_kv_heads=2,
            head_dim=16, window=16, num_dense=1, dense_width=64,
            expert_width=16, n_routed=8, experts_per_token=2,
            routed_scale=2.448))

    def granite_hybrid(ff, bs):
        build_granite_hybrid_lm(ff, bs, 16, GraniteHybridConfig(
            vocab_size=128, hidden_size=32,
            layer_types=("mamba", "attention", "mamba"), mlp_width=64,
            attention_multiplier=0.0625, mamba_heads=4, mamba_head_dim=16,
            state_size=8, chunk_size=8, num_heads=4, num_kv_heads=2))

    def zaya(ff, bs):
        build_zaya_lm(ff, bs, 16, ZayaConfig(
            vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=16, n_routed=4, expert_width=16,
            router_width=8))

    return {
        "mlp": mlp,
        "alexnet": alexnet,
        "resnet50": resnet50,
        "resnext50": resnext50,
        "inception_v3": inception_v3,
        "transformer": transformer,
        "dlrm": dlrm,
        "moe": moe,
        "xdl": xdl,
        "candle_uno": candle_uno,
        "nmt": nmt,
        "gpt": gpt,
        "latent_moe": latent_moe,
        "hybrid": hybrid,
        "sparse_hybrid": sparse_hybrid,
        "nemotron_h": nemotron_h,
        "trinity": trinity,
        "granite_hybrid": granite_hybrid,
        "zaya": zaya,
    }
