"""GLM-5.3-Flash's mechanisms at toy widths on the CPU: the residual of four
streams under Sinkhorn-projected mixes, the learned indexer over pooled
keys (its one-token and chunk forms, below and above its budget, pools
that close inside a chunk, at its end and at a decode step), KDA through
its two ranks, the SwiGLU clamp in the three forms of a gated MLP, the
state kind's chunk against the bucket prefill, and the whole toy model
(two periods of three KDA layers to one sparse latent layer behind a dense
layer, 16 experts of which 4 held, n = 4) through the builder,
``compile()`` and the paged programs against the plain reference
(``benchmark/reference/glm.py``, which imports nothing of the program).
The programs at the published widths for a described v5e are in
tests/test_tpu_lowering.py."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families import glm as family  # noqa: E402
from benchmark.reference import glm as reference  # noqa: E402
from flexflow_tpu import FFConfig, FFModel  # noqa: E402
from flexflow_tpu.ffconst import CompMode, DataType, OpType  # noqa: E402
from flexflow_tpu.models import build_latent_moe_lm  # noqa: E402
from flexflow_tpu.ops.attention import Indexer  # noqa: E402
from flexflow_tpu.ops.linear import gated_mlp  # noqa: E402
from flexflow_tpu.ops.stream_mix import sinkhorn  # noqa: E402
from flexflow_tpu.serving import GenerationInstance  # noqa: E402
from flexflow_tpu.serving.cache_entry import (  # noqa: E402
    SparseLatentEntry, StateEntry)
from flexflow_tpu.serving.generation import PagedDecoder  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                       "glm-toy.json")) as _f:
    TOY = json.load(_f)
SEED = 2 ** 31 + 63
MAX_LEN = 96
CHUNK = 16


def program(config, slots=3, seed=SEED):
    """The program's graph for ``config`` in float32 (so that a comparison
    sees formulas, not rounding), filled with the reference's weights."""
    cfg = dataclasses.replace(family.program_config(config, MAX_LEN),
                              param_dtype=DataType.FLOAT, draw_weights=True)
    ff = FFModel(FFConfig(batch_size=slots, seed=0, ledger="off",
                          search_cache="off",
                          computation_mode=CompMode.INFERENCE))
    build_latent_moe_lm(ff, slots, MAX_LEN, cfg)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    weights = reference.init_weights(config, seed)
    ff.compiled.params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), family.to_program(weights, config))
    ff.compiled.bump_params_version()
    return ff, weights


@pytest.fixture(scope="module")
def toy():
    return program(TOY)


def _tokens(n, seed=1):
    return np.random.default_rng(seed).integers(
        0, TOY["vocab_size"], n).astype(np.int32)


PADDED = 80   # the reference's one sequence length: one compilation


def _reference(weights, toks, config=TOY, **kw):
    """The reference over ``toks`` padded to ``PADDED`` tokens (every layer
    is causal: what comes behind moves nothing before it), its per-position
    outputs cut back; ``states`` are the padded sequence's and mean
    something only at ``len(toks) == PADDED``."""
    n = len(toks)
    padded = np.concatenate([toks, np.zeros(PADDED - n, np.int32)])
    out = reference.forward(weights, jnp.asarray(padded[None]), config, **kw)
    out["logits"] = out["logits"][:, :n]
    for layer in out["sparse"]:
        layer["own"] = layer["own"][:, :n, :n // 4]
    return out


# ---- the residual streams -----------------------------------------------------

def _mix_op(n=4, d=8, **attrs):
    ff = FFModel(FFConfig(batch_size=2, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    x = ff.create_tensor((2, 100, n * d), DataType.FLOAT, name="x")
    u, coefs = ff.stream_mix_pre(x, n, eps=1e-6, norm_eps=1e-5, **attrs)
    ff.stream_mix_post(x, u, coefs, n, name="post")
    return ff


def test_stream_mix_is_the_references_and_nearly_doubly_stochastic():
    """``pre`` and ``post`` against the reference's two pieces on drawn
    weights (20 rounds): the sublayer's input, the coefficients and the
    streams behind it; the rows and the columns of ``C`` sum to 1 within
    1e-3, and after 2 rounds they do not."""
    n, d = 4, 8
    ff = _mix_op(n, d)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    pre, post = ff.compiled.ops
    assert (pre.op_type, post.op_type) == (OpType.STREAM_MIX,) * 2
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 100, n * d)), jnp.float32)
    w = {"w": jnp.asarray(rng.normal(size=(n * d, 2 * n + n * n))
                          * (n * d) ** -0.5, jnp.float32),
         "scale": jnp.asarray(reference.MIX_SCALES, jnp.float32),
         "bias": reference._draw_mix_bias(jax.random.key(3), n=n,
                                          stream=1).astype(jnp.float32)}
    u, coefs = pre.forward(None, [x], w)
    y = jnp.asarray(rng.normal(size=(2, 100, d)), jnp.float32)
    (out,) = post.forward(None, [x, y, coefs], {})
    cfg = dict(TOY, hidden_size=d)
    f = reference._pieces(reference._key(cfg), "float32", "float32", 20, 3)
    xs = x.reshape(2, 100, n, d)
    u_ref, post_ref, c_ref = f["mix_pre"](xs, w)
    assert np.abs(u - u_ref).max() < 1e-5
    assert np.abs(coefs[..., :n] - post_ref).max() < 1e-6
    c = np.asarray(coefs[..., n:]).reshape(2, 100, n, n)
    assert np.abs(c - c_ref).max() < 1e-6
    # (the reference's ``mix_post`` gives its streams up: a copy)
    assert np.abs(out.reshape(xs.shape)
                  - f["mix_post"](xs + 0.0, y, post_ref, c_ref)).max() < 1e-5
    # 200 tokens: every row within 1e-3, nine columns in ten (the rounds
    # end at the rows; a token whose dynamic part is far out needs more)
    assert np.abs(c.sum(-1) - 1).max() < 1e-3
    off = np.abs(c.sum(-2) - 1).max(-1).ravel()
    assert np.quantile(off, 0.9) < 1e-3 and off.max() < 2e-2
    _, _, c3 = reference._pieces(reference._key(cfg), "float32", "float32",
                                 3, 3)["mix_pre"](xs, w)
    assert np.median(np.abs(np.asarray(c3).sum(-2) - 1).max(-1)) > 3e-3
    # the static part alone reads one stream at 0.88 and leaves C near
    # the identity
    assert 0.85 < float(jax.nn.sigmoid(w["bias"][1])) < 0.9


def test_one_stream_is_the_plain_residual():
    """``hc_mult`` 1 builds the graph it always built (no stream op), and
    the op itself refuses a path of one stream."""
    with pytest.raises(ValueError, match="plain residual"):
        _mix_op(1, 8)
    ff = FFModel(FFConfig(batch_size=2, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    cfg = dataclasses.replace(family.program_config(TOY, MAX_LEN), hc_mult=1)
    build_latent_moe_lm(ff, 2, MAX_LEN, cfg)
    types = [layer.op_type for layer in ff.layers]
    assert OpType.STREAM_MIX not in types
    assert types.count(OpType.EW_ADD) == 2 * 9 + 8     # residuals, shared


# ---- the indexer --------------------------------------------------------------

def test_selection_is_the_top_pools_and_dense_below_the_budget():
    """``Indexer`` takes the ``picks`` highest pools before the query's
    own (ties to the lower pool), as ids (``picked``) and as a mask
    (``taken``), the same pools; while a
    query has no more pools before it than ``picks`` the keys it sees are
    the causal ones: dense latent attention."""
    ix = Indexer(heads=2, dim=8, rope_dim=4, pool=4, topk=16)
    assert ix.picks == 3
    rng = np.random.default_rng(0)
    s = 40
    sc = rng.normal(size=(1, s, s // 4)).astype(np.float32)
    sc[0, :, 5] = sc[0, :, 2]                     # a tie a query
    qpos = jnp.arange(s)[None]
    before = jnp.arange(s // 4)[None, None, :] < (qpos // 4)[..., None]
    scores = jnp.where(before, sc, -jnp.inf)
    ids, taken = np.asarray(ix.picked(scores)), np.asarray(ix.taken(scores))
    for t in range(s):
        have = t // 4
        want = sorted(range(have), key=lambda p: (-sc[0, t, p], p))[:3]
        assert sorted(i for i in ids[0, t] if i >= 0) == sorted(want)
        assert sorted(np.flatnonzero(taken[0, t])) == sorted(want)
    sees = np.asarray(ix.sees(jnp.asarray(taken), qpos, qpos))
    causal = np.tril(np.ones((s, s), bool))
    dense = np.arange(s) // 4 <= ix.picks
    assert (sees[0, dense] == causal[dense]).all()
    assert (sees[0, ~dense].sum(-1) < causal[~dense].sum(-1)).all()
    # a query past the budget reads its own pool and three more
    assert (sees[0, ~dense].sum(-1)
            == 12 + np.arange(s)[~dense] % 4 + 1).all()


def _paged(dec, prompt, steps, names=()):
    """A prompt through the chunk programs, then greedy decode steps in
    slot 0: the steps' logits, the tokens, the picks and the state rows."""
    from benchmark.selected_states import as_mask

    n = len(prompt)
    pools = (n + steps) // 4
    table = dec.pool.try_admit(n + steps + 1)
    picks = {name: [] for name in names}
    for at in range(0, n, dec.prefill_chunk):
        logits = dec.prefill_chunk_at(prompt, table, at)
        for name in names:
            picks[name].append(as_mask(np.asarray(dec.last_routing[name])[
                :, :min(dec.prefill_chunk, n - at)], pools))
    rows, toks = [logits], list(prompt)
    for k in range(steps):
        toks.append(int(rows[-1].argmax()))
        tokens = np.zeros(dec.decode_slots, np.int32)
        tables = np.zeros((dec.decode_slots, dec.max_blocks_per_request),
                          np.int32)
        lens = np.zeros(dec.decode_slots, np.int32)
        tokens[0], lens[0] = toks[-1], n + k
        tables[0, :len(table)] = table
        rows.append(dec.decode(tokens, tables, lens)[0])
        for name in names:
            picks[name].append(as_mask(
                np.asarray(dec.last_routing[name])[:1], pools))
    row = int(dec.pool.rows_of(np.asarray(table)[None])[0])
    states = [np.moveaxis(np.asarray(dec.pool.kv[name][0][row]).reshape(
        kind.key_dim, kind.heads, kind.value_dim), 1, 0)
        for name, kind in dec.pool.kinds.items() if kind.name == "state"]
    dec.pool.free(table)
    return (np.stack(rows), np.asarray(toks, np.int32),
            [np.concatenate(p, axis=1) for p in picks.values()], states)


def test_whole_forward_equals_the_references(toy):
    """The whole model cache-free (every op's ``whole``: the stream mixes,
    KDA through its ranks and its gate a channel, the sparse latent op's
    selection as a mask, the clamped MLPs, the biased routing) against the
    reference's forward over 80 tokens, 20 pools against a budget of 4.
    2e-4 of the logits' range: float32 summation order."""
    ff, weights = toy
    toks = _tokens(80)
    got = PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=8,
                       calibrate=False)._dense_reference_logits(toks)
    want = np.asarray(_reference(weights, toks)["logits"])[0]
    assert want.shape == (80, TOY["vocab_size"])
    assert np.abs(got - want).max() <= 2e-4 * np.abs(want).max()
    assert reference.param_count(TOY) == sum(
        int(np.prod(a.shape)) for ws in ff.compiled.params.values()
        for a in ws.values())


@pytest.fixture(scope="module")
def instance(toy):
    inst = GenerationInstance(toy[0], decode_slots=3, block_size=8,
                              max_length=MAX_LEN, prefill_chunk=CHUNK)
    yield inst
    inst.stop()


@pytest.mark.parametrize("n", [11, 37, 48, 72])
def test_chunks_then_decode_equal_the_references_forward(toy, instance, n):
    """A prompt in chunks of 16 then 8 decode steps through
    ``GenerationInstance``'s pool against the reference's full forward
    over the whole sequence: 11 tokens (one chunk, the dense regime all
    through), 37 (a last chunk that ends inside a pool, which the decode
    steps close: 37 = 9 pools and one key), 48 (whole chunks and whole
    pools) and 72 (18 pools against 3 picks). LOGITS at 2e-4 of their
    range, the PICKS the reference's own, the state rows the
    recurrence's (where the reference ran no further than the program:
    72 + 8 tokens)."""
    ff, weights = toy
    dec = instance.decoder
    names = family.sparse_layer_names(TOY)
    rows, toks, picks, states = _paged(dec, _tokens(n, seed=n), 8, names)
    out = _reference(weights, toks)
    want = np.asarray(out["logits"])[0][len(toks) - len(rows):]
    assert np.abs(rows - want).max() <= 2e-4 * np.abs(want).max()
    for got, layer in zip(picks, out["sparse"]):
        own = np.asarray(layer["own"])
        assert got.shape == own.shape == (1, n + 8, (n + 8) // 4)
        assert (got == own).all()
        assert (got.sum(-1)[0] == np.minimum(np.arange(n + 8) // 4, 3)).all()
    assert len(states) == 7
    for got, want_state in zip(states, out["states"] if n + 8 == PADDED
                               else ()):
        assert np.abs(got - np.asarray(want_state)[0]).max() \
            <= 1e-4 * np.abs(want_state).max()


def test_the_three_kinds_share_one_pool_and_its_books(toy, instance):
    """``kind_for`` gives the KDA layers the state kind and the sparse
    layers the indexed latent kind; the pool's bytes are the sum of what
    they say; the books count the index's steps and chunks."""
    dec = instance.decoder
    kinds = dec.pool.kinds
    assert [type(kinds[f"block{i}_attn"]) for i in range(9)] == [
        StateEntry, SparseLatentEntry, StateEntry, StateEntry, StateEntry,
        SparseLatentEntry, StateEntry, StateEntry, StateEntry]
    state, sparse = kinds["block0_attn"], kinds["block1_attn"]
    assert state == StateEntry(2, 16, 16, 3, 3 * 2 * 16, True)
    assert sparse.index == Indexer(2, 16, 8, 4, 16) and sparse.chunked
    per_request = (7 * state.request_bytes(jnp.float32)
                   + 2 * sparse.request_bytes(jnp.float32))
    assert sparse.request_bytes(jnp.float32) == 16 * 4
    per_token = 2 * sparse.token_bytes(jnp.float32)
    assert per_token == 2 * (128 * 4 + 16 * 4 // 4)   # a row, a key a four
    assert dec.pool.memory_bytes() == (
        dec.pool.num_rows * per_request
        + dec.pool.num_blocks * dec.pool.block_size * per_token)
    assert dec.attention_path_by_entry == {
        "state": {"decode": "gather", "chunk": "scan"},
        "sparse_latent": {"decode": "gather", "chunk": "scan"}}
    # through the scheduler: its steps and chunks reach the books
    out = instance.generate(_tokens(40, seed=9), max_new_tokens=6,
                            temperature=0.0)
    assert out.shape == (46,)
    ix = instance.stats()["kv"]["index"]
    # steps at cached lengths 40..44 (the last token is not stepped)
    lens = np.arange(40, 45)
    assert ix["rows_live"] == int((lens + 1).sum())
    assert ix["rows_read"] == int((3 * 4 + lens % 4 + 1).sum())
    assert ix["pools_scored"] == int((lens // 4).sum())
    assert ix["dense_steps"] == 0
    pos = np.arange(40)
    assert ix["rows_taken"] == int(
        (np.minimum(pos // 4, 3) * 4 + pos % 4 + 1).sum())
    # the first chunk is dense and walks one span of 512 keys; the other
    # two gather each query's budget of 16 rows
    assert ix["rows_attended"] == 16 * 512 + 24 * 16
    st = instance.stats()["kv"]["state"]
    assert (st["rows_started"], st["rows_carried"]) == (9, 18)


def test_what_the_kinds_do_not_define_refuses_by_name(toy):
    ff, _ = toy
    with pytest.raises(ValueError, match="no int8 form"):
        PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=8,
                     kv_dtype="int8")
    with pytest.raises(ValueError, match="speculative verify"):
        GenerationInstance(ff, decode_slots=3, block_size=8,
                           max_length=MAX_LEN, spec_k=2, draft_ff=ff)
    with pytest.raises(ValueError, match="not whole pools"):
        PagedDecoder(ff, MAX_LEN, decode_slots=3, block_size=6)


def test_the_family_refuses_what_it_does_not_implement():
    family.check(TOY)
    for key, value in (("index_kpool", 2), ("index_kpool_compress", False),
                       ("index_kpool_always_select_tail", False),
                       ("mhc", False), ("mla_use_nope", False),
                       ("scoring_func", "softmax"), ("n_group", 2)):
        with pytest.raises(ValueError, match=key):
            family.check(dict(TOY, **{key: value}))
    with pytest.raises(ValueError, match="implements no key"):
        family.check(dict(TOY, index_kpool_reduction="max"))
    with pytest.raises(ValueError, match="published pattern"):
        family.check(dict(TOY, layer_types=TOY["layer_types"][::-1]))


# ---- the state kind's chunk ---------------------------------------------------

def _state_rows(dec, table):
    row = int(dec.pool.rows_of(np.asarray(table)[None])[0])
    return [(np.asarray(dec.pool.kv[name][0][row]),
             np.asarray(dec.pool.kv[name][1][row]))
            for name, kind in dec.pool.kinds.items() if kind.name == "state"]


@pytest.mark.parametrize("model", ["hybrid", "ling"])
def test_state_chunk_leaves_what_the_bucket_prefill_leaves(model):
    """A prompt of 21 tokens through ``StateEntry.chunk`` in chunks of 8
    (the last one padded) leaves in the request's row the state and the
    convolution tails the bucket prefill leaves at the same length, for
    ``GATED_DELTA_NET`` (the hybrid toy) and ``KIMI_DELTA_ATTENTION`` (a
    Ling toy of KDA layers alone: its latent row takes no chunks)."""
    from flexflow_tpu.models import (HybridLMConfig, LatentMoEConfig,
                                     build_hybrid_lm)

    ff = FFModel(FFConfig(batch_size=2, seed=3, ledger="off",
                          search_cache="off",
                          computation_mode=CompMode.INFERENCE))
    if model == "hybrid":
        build_hybrid_lm(ff, 2, 32, HybridLMConfig(
            vocab_size=64, hidden_size=32, num_heads=2, linear_heads=2,
            linear_key_dim=8, linear_value_dim=16, mlp_width=48,
            layer_types=("linear_attention", "linear_attention")))
    else:
        build_latent_moe_lm(ff, 2, 32, LatentMoEConfig(
            vocab_size=64, max_positions=32, hidden_size=32, num_layers=2,
            num_heads=2, first_dense=2, dense_width=48,
            layer_types=("kda", "kda"), kda_head_dim=16))
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    prompt = np.random.default_rng(5).integers(0, 64, 21).astype(np.int32)
    got = []
    for kw in (dict(prefill_buckets=[32]), dict(prefill_chunk=8)):
        dec = PagedDecoder(ff, 32, decode_slots=2, block_size=8,
                           calibrate=False, **kw)
        table = dec.pool.try_admit(24)
        logits = dec.prefill(prompt, table)
        got.append((logits, _state_rows(dec, table)))
    (whole, rows_whole), (chunked, rows_chunked) = got
    assert len(rows_whole) == 2
    assert np.abs(whole - chunked).max() <= 2e-4 * np.abs(whole).max()
    for (s0, t0), (s1, t1) in zip(rows_whole, rows_chunked):
        assert np.abs(s0 - s1).max() <= 1e-4 * max(np.abs(s0).max(), 1e-3)
        assert np.abs(t0 - t1).max() <= 1e-5


# ---- the clamp ------------------------------------------------------------------

def test_the_clamp_in_the_three_forms_of_a_gated_mlp(monkeypatch):
    """``limit`` cuts the gate from above and the up-projection on both
    sides before the product, in ``gated_mlp``, in the routed experts'
    dense and grouped forms and in the grouped kernel (interpreted), each
    against the plain formula; without it each is what it was."""
    from flexflow_tpu.kernels.grouped_experts import grouped_experts

    rng = np.random.default_rng(0)
    t, e, w, count = 16, 256, 128, 4
    x = jnp.asarray(rng.normal(size=(t, e)) * 3, jnp.float32)
    mats = {k: jnp.asarray(rng.normal(size=(count, a, b)) * a ** -0.5,
                           jnp.float32)
            for k, (a, b) in (("w_gate", (e, w)), ("w_up", (e, w)),
                              ("w_down", (w, e)))}
    ids = jnp.asarray(rng.integers(0, count, (t, 2)), jnp.int32)
    ids = ids.at[:, 1].set((ids[:, 0] + 1) % count)
    gates = jnp.asarray(rng.uniform(0.2, 1, (t, 2)), jnp.float32)

    def plain(limit):
        out = np.zeros((t, e), np.float32)
        for i in range(t):
            for j in range(2):
                c = int(ids[i, j])
                g, u = x[i] @ mats["w_gate"][c], x[i] @ mats["w_up"][c]
                if limit is not None:
                    g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
                out[i] += gates[i, j] * ((jax.nn.silu(g) * u)
                                         @ mats["w_down"][c])
        return out

    limit = 1.0
    assert np.abs(plain(limit) - plain(None)).max() > 0.1   # it binds
    one = np.asarray(gated_mlp(x, mats["w_gate"][0], mats["w_up"][0],
                               mats["w_down"][0], limit=limit))
    g, u = x @ mats["w_gate"][0], x @ mats["w_up"][0]
    want_one = (jax.nn.silu(jnp.minimum(g, limit))
                * jnp.clip(u, -limit, limit)) @ mats["w_down"][0]
    assert np.abs(one - want_one).max() < 1e-5
    for lim in (limit, None):
        ff = FFModel(FFConfig(batch_size=t, ledger="off",
                              computation_mode=CompMode.INFERENCE))
        xin = ff.create_tensor((t, 1, e), DataType.FLOAT, name="x")
        ff.routed_experts(xin, n_routed=count, experts_per_token=2, width=w,
                          limit=lim, name="experts")
        ff.compile(optimizer=None, loss_type=None, metrics=[])
        (op,) = [o for o in ff.compiled.ops if o.name == "experts"]
        assert ("limit" in op.attrs) == (lim is not None)
        for form in (op._apply_dense, op._apply_grouped):
            assert np.abs(np.asarray(form(mats, x, ids, gates))
                          - plain(lim)).max() < 1e-4
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    bf = {k: v.astype(jnp.bfloat16) for k, v in mats.items()}
    for lim in (limit, None):
        got, _ = grouped_experts(
            x.astype(jnp.bfloat16), ids, gates, bf, first=0, gated=True,
            **({} if lim is None else {"limit": lim}))
        want = plain(lim)
        assert np.abs(np.asarray(got, np.float32) - want).max() \
            < 0.05 * np.abs(want).max()


def test_the_reference_reads_every_new_weight_and_the_clamp(toy):
    """A weight of this family's own, set to what leaving it out would
    mean, moves the reference's logits; so do the clamp, the count of
    Sinkhorn rounds and the count of pools taken: the comparison above
    would see a program that dropped any."""
    _, weights = toy
    toks = _tokens(PADDED, seed=3)
    want = np.asarray(_reference(weights, toks)["logits"])[0]

    def moved(logits):
        return np.abs(np.asarray(logits)[0] - want).max() \
            > 1e-3 * np.abs(want).max()

    for leaf, value in (("l0.wf_a", 0.0), ("l0.wg_b", 0.0),
                        ("l1.k_bias_i", 0.0), ("l1.ww_i", 1.0),
                        ("l1.mix1.bias", 0.0), ("l1.mix2.w", 0.0)):
        changed = dict(weights)
        changed[leaf] = jnp.full_like(weights[leaf], value)
        assert moved(_reference(changed, toks)["logits"]), leaf
    assert moved(_reference(weights, toks,
                            config=dict(TOY, swiglu_limit=None))["logits"])
    assert moved(_reference(weights, toks, sinkhorn_iters=1,
                            picks=1)["logits"])


# ---- the share ------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_expert_layer():
    """Four holders, every one routing over all sixteen experts under the
    same bias and adding nothing for the experts it lacks: their routed
    parts, and the shared expert counted once, are the uncut layer."""
    whole = dict(TOY, n_routed_experts=16, expert_first=0)
    weights = reference.init_weights(whole, SEED)
    w = reference._layer(weights, 1)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 9, 64)),
                    jnp.float32)

    def pieces(config):
        return reference._pieces(reference._key(config), "float32",
                                 "float32", 20, 3)

    f = pieces(whole)
    u = f["normed"](x, w["norm2"])
    s = f["scores_of"](u, w)
    ids = f["choose"](s, w["bias"])
    want = np.asarray(f["expert_ffn"](u, w, s, ids))
    only_shared = dict(w, **{"experts." + k: jnp.zeros_like(w["experts." + k])
                             for k in ("gate", "up", "down")})
    shared = np.asarray(f["expert_ffn"](u, only_shared, s, ids))
    assert float(np.abs(np.asarray(w["bias"], np.float32)).max()) > 0
    total = np.zeros_like(want)
    for first in range(0, 16, 4):
        part = dict(TOY, n_routed_experts=4, expert_first=first)
        held = dict(w, **{"experts." + k: w["experts." + k][first:first + 4]
                          for k in ("gate", "up", "down")})
        total += np.asarray(pieces(part)["expert_ffn"](u, held, s, ids)) \
            - shared
    assert np.abs(shared).max() > 0
    assert np.abs(total + shared - want).max() < 1e-5 * np.abs(want).max()


def test_the_older_families_graphs_are_op_for_op_what_they_were():
    """``ling``'s and ``axk1``'s toys through the same builder: no stream
    op, no indexer, no clamp, no rank among their ops' attributes."""
    for fam, name in (("ling", "ling-toy"), ("axk1", "axk1-toy")):
        path = os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                            name + ".json")
        if not os.path.exists(path):
            continue
        with open(path) as f:
            config = json.load(f)
        mod = __import__(f"benchmark.families.{fam}", fromlist=["x"])
        ff = FFModel(FFConfig(batch_size=2, ledger="off",
                              computation_mode=CompMode.INFERENCE))
        mod.build(ff, config, 2, 32)
        for layer in ff.layers:
            assert layer.op_type is not OpType.STREAM_MIX
            assert not {"limit", "indexer", "decay_rank", "gate_rank"} \
                & set(layer.attrs), (layer.name, sorted(layer.attrs))


def test_the_reference_in_segments_is_the_reference(toy, monkeypatch):
    """The reference runs a KDA mixer and the dense MLP a segment of the
    sequence at a time (at the published widths 9,000 tokens do not fit
    beside the program otherwise), carrying the state and the
    convolution's last inputs: segments of 32 and of 7 tokens give what one
    segment gives."""
    _, weights = toy
    toks = _tokens(PADDED, seed=8)
    want = _reference(weights, toks)
    for segment in (32, 7):
        monkeypatch.setattr(reference, "SEGMENT", segment)
        got = _reference(weights, toks)
        assert np.abs(got["logits"] - want["logits"]).max() \
            <= 1e-5 * np.abs(want["logits"]).max()
        for a, b in zip(got["states"], want["states"]):
            assert np.abs(a - b).max() <= 1e-5 * np.abs(b).max()


def test_the_dense_cache_generates_what_the_paged_programs_do(toy, instance):
    """``Generator`` (every kind's ``dense_step``: the sparse latent op's
    selection over a dense cache of rows and index keys, the KDA state and
    tails carried) and the paged chunk and decode programs write the same
    greedy continuation of a 37-token prompt across the change of regime."""
    from flexflow_tpu.serving.generation import Generator

    ff, _ = toy
    prompt = _tokens(37, seed=4)
    dense = Generator(ff, MAX_LEN, 1).generate(prompt[None], 8)[0]
    _, toks, _, _ = _paged(instance.decoder, prompt, 8)
    assert dense.shape == (45,)
    assert (dense == toks[:45]).all()
