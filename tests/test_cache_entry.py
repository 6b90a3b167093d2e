"""The seam of serving/cache_entry.py: a new entry kind is a class and a
line in ``KINDS`` (generation.py, kv_cache.py and scheduler.py serve it
unedited), and each real kind's arenas, bytes, names and limits are the
kind's own answers."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.ffconst import CompMode, OpType
from flexflow_tpu.models import (GPTConfig, LatentMoEConfig, build_gpt,
                                 build_latent_moe_lm)
from flexflow_tpu.serving import (ContinuousBatchingScheduler,
                                  GenerationInstance, Generator,
                                  PagedDecoder, PagedKVPool)
from flexflow_tpu.ops import rows as rows_ops
from flexflow_tpu.serving import cache_entry
from flexflow_tpu.serving.cache_entry import (Int8PairEntry, LatentEntry,
                                              PairEntry, StateEntry)
from flexflow_tpu.sim import serving_kv_pool_bytes

V = 50
NB, BS = 9, 8


def _model(build, *args):
    ff = FFModel(FFConfig(batch_size=4, seed=0, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    build(ff, 4, *args)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    return ff


@pytest.fixture(scope="module")
def gpt():
    return _model(build_gpt, 6, GPTConfig(
        vocab_size=V, max_positions=32, hidden_size=32, num_heads=4,
        num_layers=2))


@pytest.fixture(scope="module")
def latent_lm():
    return _model(build_latent_moe_lm, 8, LatentMoEConfig(
        vocab_size=V, max_positions=32, hidden_size=32, num_layers=2,
        num_heads=4, q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, dense_width=32, expert_width=16,
        n_routed=4, experts_per_token=2))


# ---- (a) a kind the package has never seen -----------------------------------

@dataclasses.dataclass(frozen=True)
class _FusedEntry(PairEntry):
    """Keys and values of a token side by side in ONE arena row."""

    name = "fused"

    def arenas(self, num_blocks, block_size, dtype):
        return (jax.ShapeDtypeStruct(
            (num_blocks, block_size, 2 * self.heads * self.head_dim), dtype),)

    def write(self, entry, flat, kh, vh):
        t = kh.shape[0]
        rows = jnp.concatenate([kh.reshape(t, -1), vh.reshape(t, -1)], -1)
        return (cache_entry._put(entry[0], flat, rows),)

    def read(self, entry, tables):
        n, hd = tables.shape[0], self.heads * self.head_dim
        rows = entry[0][tables].reshape(n, -1, 2 * hd)
        shape = (n, rows.shape[1], self.heads, self.head_dim)
        return rows[..., :hd].reshape(shape), rows[..., hd:].reshape(shape)

    def reads_in_place(self, op, entry, slots, window, max_blocks):
        return False


def test_a_new_kind_serves_through_unmodified_decoder_and_scheduler(
        gpt, monkeypatch):
    rng = np.random.default_rng(7)
    reqs = [(rng.integers(0, V, (n,)).astype(np.int32), m)
            for n, m in [(3, 6), (6, 2), (2, 9), (5, 4)]]
    gen = Generator(gpt, max_length=32)
    want = [gen.generate(p[None, :], m)[0] for p, m in reqs]
    monkeypatch.setitem(cache_entry.KINDS, OpType.MULTIHEAD_ATTENTION,
                        _FusedEntry.for_op)
    sched = ContinuousBatchingScheduler(gpt, max_length=32, decode_slots=3,
                                        block_size=8)
    try:
        futs = [sched.submit(p, m) for p, m in reqs]
        got = [f.result(timeout=120) for f in futs]
        stats = sched.stats()
    finally:
        sched.stop()
    for out, ref in zip(got, want):
        np.testing.assert_array_equal(out, ref)
    pool = sched.decoder.pool
    assert stats["kv"]["entry"] == "fused"
    assert stats["kv"]["attention_path"]["decode"] == "gather"
    assert all(isinstance(k, _FusedEntry) for k in pool.kinds.values())
    assert {tuple(a.shape for a in e) for e in pool.kv.values()} == {
        ((pool.num_blocks, 8, 2 * 32),)}
    # a speculative verify window goes through the same kind
    dec = PagedDecoder(gpt, 32, decode_slots=2, block_size=8)
    table = dec.pool.try_admit(12)
    dec.prefill(reqs[0][0], table)
    tables = np.zeros((2, dec.max_blocks_per_request), np.int32)
    tables[0] = table
    window = np.zeros((2, 3), np.int32)
    window[0] = want[0][3:6]
    logits = dec.verify(window, tables, np.array([3, 0], np.int32))
    assert logits[0].argmax(-1).tolist() == want[0][4:7].tolist()


def test_an_attention_op_without_a_kind_is_refused_by_name(gpt, monkeypatch):
    monkeypatch.delitem(cache_entry.KINDS, OpType.MULTIHEAD_ATTENTION)
    with pytest.raises(ValueError, match=r"block0_attn.*no cache entry kind "
                                         r".*MULTIHEAD_ATTENTION"):
        PagedDecoder(gpt, 32, decode_slots=2, block_size=8)


# ---- (b) the three real kinds ------------------------------------------------

CASES = {
    "pair": (PairEntry(4, 8), "float32",
             [((NB, BS, 32), "float32")] * 2, {"entry": "pair"}, None),
    "pair-bf16": (PairEntry(4, 8), "bfloat16",
                  [((NB, BS, 32), "bfloat16")] * 2, {"entry": "pair"}, None),
    "int8": (PairEntry(4, 8), "int8",
             [((NB, BS, 32), "int8")] * 2 + [((NB, BS, 4), "float32")] * 4,
             {"entry": "int8"}, None),
    "latent": (LatentEntry(24), "bfloat16", [((NB, BS, 128), "bfloat16")],
               {"entry": "latent", "row_width": 24, "row_lanes": 128}, 1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_kind_answers_for_its_arenas_bytes_names_and_limits(case):
    kind, kv_dtype, arenas, said, max_window = CASES[case]
    pool = PagedKVPool({"a": kind, "b": kind}, num_blocks=NB, block_size=BS,
                       max_blocks_per_request=4, kv_dtype=kv_dtype)
    stored = pool.kinds["a"]
    assert isinstance(stored, Int8PairEntry) == (kv_dtype == "int8")
    for entry in pool.kv.values():
        assert [(a.shape, str(a.dtype)) for a in entry] == arenas
    store = jnp.bfloat16 if kv_dtype == "bfloat16" else jnp.float32
    held = sum(a.nbytes for entry in pool.kv.values() for a in entry)
    assert 2 * NB * BS * stored.token_bytes(store) == held
    assert pool.memory_bytes() == held == serving_kv_pool_bytes(
        pool.specs, NB, BS, kv_dtype)
    st = pool.stats()
    assert {k: st[k] for k in said} == said and st["memory_bytes"] == held
    assert stored.max_window == max_window
    dense = stored.dense_shapes(2, 16, jnp.float32)
    assert len(dense) == (1 if case == "latent" else 2)
    assert all(a.shape[:2] == (2, 16) and a.dtype == jnp.float32
               for a in dense)


@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16"])
def test_a_state_kind_answers_for_its_rows_bytes_names_and_limits(kv_dtype):
    """Beside a pair kind in one pool: arenas by rows, the state float32
    whatever ``kv_dtype`` says, the bytes a term a token and a term a
    request, each kind named with its count of ops."""
    state, pair = StateEntry(4, 8, 16, 3, 128), PairEntry(4, 8)
    pool = PagedKVPool({"s0": state, "a": pair, "s1": state}, num_blocks=NB,
                       block_size=BS, max_blocks_per_request=4,
                       kv_dtype=kv_dtype, num_rows=5)
    store = jnp.bfloat16 if kv_dtype == "bfloat16" else jnp.float32
    for name in ("s0", "s1"):
        assert [(a.shape, a.dtype) for a in pool.kv[name]] == [
            ((5, 8, 64), jnp.float32), ((5, 3 * 128), store)]
    assert pool.kv["a"][0].shape == (NB, BS, 32)
    held = sum(a.nbytes for entry in pool.kv.values() for a in entry)
    row = 8 * 64 * 4 + 3 * 128 * jnp.dtype(store).itemsize
    assert state.request_bytes(store) == row and state.keeps_row
    assert state.token_bytes(store) == 0 == len(state.arenas(NB, BS, store))
    assert pool.memory_bytes() == held == (
        NB * BS * pair.token_bytes(store) + 5 * 2 * row)
    assert held == serving_kv_pool_bytes(pool.specs, NB, BS, kv_dtype,
                                         num_rows=5)
    st = pool.stats()
    assert st["entry"] == {"state": 2, "pair": 1}
    assert st["state_dtype"] == "float32"
    assert st["state"] == {"rows": 5, "in_use": 0, "high_water": 0,
                           "row_bytes": 2 * row}
    assert state.max_window == 1 and state.int8_form is None
    dense = state.dense_shapes(2, 16, store)
    assert [(a.shape, a.dtype) for a in dense] == [
        ((2, 4, 8, 16), jnp.float32), ((2, 3, 128), store)]
    with pytest.raises(ValueError, match="s0: a state cache entry has no "
                                         "int8 form"):
        PagedKVPool({"s0": state}, num_blocks=4, block_size=8,
                    max_blocks_per_request=2, kv_dtype="int8", num_rows=3)
    with pytest.raises(ValueError, match="num_rows 1 < 2"):
        PagedKVPool({"s0": state}, num_blocks=4, block_size=8,
                    max_blocks_per_request=2, num_rows=1)


# ---- what a token and a request weigh, kind by kind, pinned ---------------------

def _nine_kinds():
    from flexflow_tpu.ops.block_sparse_attention import Selection
    from flexflow_tpu.serving.cache_entry import (CcaEntry, DecayStateEntry,
                                                  SparseEntry, SsmStateEntry,
                                                  WindowEntry)

    return {
        "pair": (PairEntry(4, 8, 8), "bfloat16"),
        "int8": (PairEntry(4, 8), "int8"),
        "window": (WindowEntry(2, 8, 4, 32, value_dim=4, sink=True),
                   "bfloat16"),
        "cca": (CcaEntry(2, 16, 8, tail=2, channels=96), "bfloat16"),
        "latent": (LatentEntry(24), "bfloat16"),
        "state": (StateEntry(4, 8, 16, 3, 128), "bfloat16"),
        "sparse": (SparseEntry(2, 8, Selection(**GEOM)), "bfloat16"),
        "decay_state": (DecayStateEntry(4, 8), "bfloat16"),
        "ssm_state": (SsmStateEntry(4, 8, 16, 3, 96), "bfloat16"),
    }


# a pool of two ops of the kind, 9 blocks of 16 tokens and 5 rows: the
# bytes of its token term and of its row term (``pool_bytes``), ``token_bytes
# + request_bytes`` of one op, the op's entry (token arenas, then request
# arenas) and a request's ``row_bytes``; recorded on 3d52808, the commit
# before ``per_request`` went: a kind says ``arenas`` for what it keeps a
# token and ``request_arenas`` for what it keeps a request, and none of
# these numbers knows
_PINNED = {
    "pair": (36864, 0, 128, [((9, 16, 32), "bfloat16")] * 2, None),
    "int8": (36864, 0, 128, [((9, 16, 32), "int8")] * 2
             + [((9, 16, 4), "float32")] * 4, None),
    "window": (0, 15360, 1536, [((10, 16, 16), "bfloat16"),
                                ((10, 16, 8), "bfloat16")], 3072),
    "cca": (36864, 4160, 544, [((9, 16, 32), "bfloat16")] * 2
            + [((5, 192), "bfloat16"), ((5, 16), "bfloat16")], 832),
    "latent": (73728, 0, 256, [((9, 16, 128), "bfloat16")], None),
    "state": (0, 28160, 2816, [((5, 8, 64), "float32"),
                               ((5, 384), "bfloat16")], 5632),
    "sparse": (20736, 0, 72, [((9, 2, 16, 8), "bfloat16")] * 2
               + [((36, 16), "bfloat16")], None),
    "decay_state": (0, 10240, 1024, [((5, 4, 8, 8), "float32")], 2048),
    "ssm_state": (0, 26240, 2624, [((5, 16, 32), "float32"),
                                   ((5, 288), "bfloat16")], 5248),
}


@pytest.mark.parametrize("name", sorted(_PINNED))
def test_what_a_token_and_a_request_weigh_is_what_it_was(name):
    """The pool's arithmetic and its books for each of the nine kinds, at
    one toy shape, against the numbers of the commit before the kinds
    took to declaring a request's arenas one way: ``pool_bytes``' two
    terms, the bytes of one token and one request of one op, the entry's
    arrays in their order, ``stats()["entry"]`` and ``stats()["state"]``
    after one admission, one step of two slots and two chunks."""
    from flexflow_tpu.serving.kv_cache import pool_bytes

    kind, kv_dtype = _nine_kinds()[name]
    by_token, by_row, one, arenas, row_bytes = _PINNED[name]
    pool = PagedKVPool({"a": kind, "b": kind}, num_blocks=9, block_size=16,
                       max_blocks_per_request=4, kv_dtype=kv_dtype,
                       num_rows=5)
    store = jnp.bfloat16 if kv_dtype == "bfloat16" else jnp.float32
    stored = pool.kinds["a"]
    assert pool_bytes(pool.specs, 9, 16, kv_dtype, jnp.float32, 0) == by_token
    assert pool_bytes(pool.specs, 0, 0, kv_dtype, jnp.float32, 5) == by_row
    assert stored.token_bytes(store) + stored.request_bytes(store) == one
    assert bool(stored.request_arenas(2, 16, store)) == stored.keeps_row \
        == (row_bytes is not None)
    for entry in pool.kv.values():
        assert [(a.shape, str(a.dtype)) for a in entry] == arenas
    assert pool.memory_bytes() == by_token + by_row == sum(
        a.nbytes for entry in pool.kv.values() for a in entry)
    table = pool.try_admit(40)
    pool.count_step(np.asarray([40, 3]))
    pool.count_chunk(0)
    pool.count_chunk(16)
    st = pool.stats(np.asarray([40]))
    assert st["entry"] == name
    if row_bytes is None:
        assert "state" not in st and pool.rows_of(table[None]) is None
        return
    assert st["state"] == {
        "rows": 5, "in_use": 1, "high_water": 1, "row_bytes": row_bytes,
        "rows_stepped": 4, "rows_started": 2, "rows_carried": 2}
    assert pool.rows_of(table[None]).tolist() == [1]
    pool.free(table)
    assert pool.stats()["state"]["in_use"] == 0


_ROWS = {   # slot n writes arena row rows[n]; 0 is the null row (an idle slot)
    "all_live_in_order": [1, 2, 3, 4, 5, 6],
    "all_live_any_order": [5, 2, 6, 1, 4, 3],
    "several_idle": [4, 0, 2, 0, 0, 6],
    "one_live": [0, 0, 0, 3, 0, 0],
    "none_live": [0, 0, 0, 0, 0, 0],
}


def _bits(a):
    return np.array(jnp.asarray(a).astype(jnp.float32)).view(np.uint32)


def _odd_values(rng, shape):
    """Normal draws with a subnormal and a negative zero among them."""
    x = np.asarray(rng.standard_normal(shape), np.float32)
    flat = x.reshape(-1)
    flat[1::11], flat[5::13] = 1e-40, -0.0
    return x


def _decay_rows_case(rows, rng):
    """``decay_step_rows`` over an arena (7, H, D, D), and the same on
    the slots' rows gathered (``decay_step``) with a scatter back."""
    from flexflow_tpu.ops.lightning_attention import (decay_step,
                                                      decay_step_rows)

    n, h, d = len(rows), 2, 8
    arena = jnp.asarray(_odd_values(rng, (7, h, d, d))).at[1].set(0.0)
    q, k, v = (jnp.asarray(_odd_values(rng, (n, h, d))).at[3].set(jnp.nan)
               for _ in range(3))
    lam = jnp.asarray([0.9, 0.5], jnp.float32)
    o, got = jax.jit(decay_step_rows)(arena, rows, q, k, v, lam)
    o_ref, new = jax.jit(decay_step)(arena[rows], q, k, v, lam)
    return arena, got, arena.at[rows].set(new), o, o_ref


def _ssd_rows_case(rows, rng):
    """``ssd_step_rows`` over an arena (7, S, H P) in the pool's layout,
    and ``ssd_step`` on the slots' rows gathered, turned to the op's (H,
    P, S) and back, with a scatter back."""
    from flexflow_tpu.ops.mamba2 import ssd_step, ssd_step_rows

    n, h, p, size, g = len(rows), 4, 8, 8, 2
    arena = jnp.asarray(_odd_values(rng, (7, size, h * p))).at[1].set(0.0)
    u = jnp.asarray(_odd_values(rng, (n, h, p))).at[3].set(jnp.nan)
    decay = jnp.asarray(rng.uniform(0.2, 1.0, (n, h)), jnp.float32)
    bm, cm = (jnp.asarray(_odd_values(rng, (n, g, size))) for _ in range(2))
    y, got = jax.jit(ssd_step_rows)(arena, rows, u, decay, bm, cm)

    def gathered(arena):
        state = jnp.swapaxes(arena[rows], 1, 2).reshape(n, h, p, size)
        y, state = ssd_step(state, u, decay, bm, cm)
        return y, jnp.swapaxes(state.reshape(n, h * p, size), 1, 2)

    y_ref, new = jax.jit(gathered)(arena)
    return arena, got, arena.at[rows].set(new), y, y_ref


_ROW_STEPS = {"decay": _decay_rows_case, "ssd": _ssd_rows_case}


@pytest.mark.parametrize("case", list(_ROWS))
@pytest.mark.parametrize("form", ["bfloat16", "float32", "decay", "ssd"])
def test_rows_spread_by_one_hot_equal_the_scatter_bit_for_bit(form, case):
    """What a state kind's step writes its convolution tails with: each
    arena row takes the values of the slot that names it, against
    ``arena.at[rows].set(new)``: the stepped rows BIT for bit in both
    storage dtypes (values of every magnitude a float holds, subnormals,
    negative zeros and a slot of NaN among them: nothing is computed, so
    a slot's NaN stays in its row), the rows nobody names as they were,
    and row 0 never written, whatever the idle slots carry. The two row
    steps that take their slots' inputs through the same hand-over
    (``decay``: ``decay_step_rows``; ``ssd``: ``ssd_step_rows``) are held
    to the step on the gathered rows and a scatter back in the same
    words, and their live slots' outputs to that step's: slot 3's NaN in
    its own row and its own output, and nowhere else."""
    rows = np.asarray(_ROWS[case], np.int32)
    n, r, width = len(rows), 7, 384
    rng = np.random.default_rng(len(case))
    poisoned = {int(rows[3])} - {0}
    clean = sorted(set(range(r)) - poisoned)
    untouched = sorted(set(range(r)) - set(rows.tolist()))
    if form in _ROW_STEPS:
        arena, got, scattered, o, o_ref = _ROW_STEPS[form](
            jnp.asarray(rows), rng)
        want = _bits(scattered)
        want[0] = _bits(arena)[0]
        np.testing.assert_array_equal(_bits(got), want)
        np.testing.assert_array_equal(_bits(got)[untouched],
                                      _bits(arena)[untouched])
        assert np.isfinite(np.asarray(got)[clean]).all()
        live = (rows != 0) & (np.arange(n) != 3)
        np.testing.assert_allclose(np.asarray(o)[live],
                                   np.asarray(o_ref)[live], rtol=1e-5,
                                   atol=1e-6)
        assert np.isnan(np.asarray(o)[3]).any() == bool(poisoned)
        assert np.isfinite(np.asarray(o)[np.arange(n) != 3]).all()
        return
    dtype = form

    def draw(shape):
        x = (rng.standard_normal(shape)
             * 10.0 ** rng.integers(-44, 38, size=shape))
        x[..., ::7] = -0.0
        return jnp.asarray(x, jnp.float32).astype(dtype)

    arena = draw((r, width)).at[0].set(7.0)
    new = draw((n, width)).at[3].set(jnp.nan)   # slot 3: live in all but one
    new = jnp.where((rows == 0)[:, None] & (jnp.arange(n) != 3)[:, None],
                    jnp.asarray(3e38, dtype), new)
    got = jax.jit(rows_ops.spread_rows, donate_argnums=0)(
        jnp.array(arena), jnp.asarray(rows), new)
    want = _bits(arena.at[rows].set(new))
    want[0] = _bits(arena)[0]        # the scatter lets idle slots race here
    assert got.dtype == arena.dtype and got.shape == arena.shape
    np.testing.assert_array_equal(_bits(got), want)
    np.testing.assert_array_equal(_bits(got)[untouched],
                                  _bits(arena)[untouched])
    assert np.isfinite(np.asarray(got.astype(jnp.float32))[clean]).all()


# the zoo's toy of each kind that keeps a row a request, and its pool's blocks
_ROW_KEEPERS = {"state": ("hybrid", 8), "decay_state": ("sparse_hybrid", 4),
                "ssm_state": ("granite_hybrid", 8), "cca": ("zaya", 8)}


@pytest.mark.parametrize("kind_name", sorted(_ROW_KEEPERS))
def test_a_nan_in_one_requests_row_reaches_no_other_requests_logits(
        kind_name):
    """Two requests decode side by side, a third slot idle; the row the
    second holds in every per-request arena is made NaN: the first's
    logits over three decode steps are, bit for bit, what they are
    without it, and the second's are NaN. Each kind hands its slots'
    values to its rows through ``ops/rows.py``, a take: a product over
    the slots (``decay_state`` up to PR 59) made every live row NaN."""
    from flexflow_tpu.models import zoo_smoke_builders

    model, block = _ROW_KEEPERS[kind_name]
    ff = FFModel(FFConfig(batch_size=2, seed=0, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    zoo_smoke_builders()[model](ff, 2)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    rng = np.random.default_rng(3)
    a, b = (rng.integers(0, 128, n).astype(np.int32) for n in (11, 9))

    def run(poison):
        dec = PagedDecoder(ff, 32, decode_slots=3, block_size=block,
                           prefill_buckets=[16], calibrate=False)
        assert kind_name in {k.name for k in dec.pool.kinds.values()}
        ta, tb = dec.pool.try_admit(24), dec.pool.try_admit(24)
        first = dec.prefill(a, ta)
        dec.prefill(b, tb)
        if poison:
            row = int(dec.pool.rows_of(tb[None])[0])
            for name, kind in dec.pool.kinds.items():
                held = len(kind.arenas(2, block, jnp.float32))
                dec.pool.kv[name] = dec.pool.kv[name][:held] + tuple(
                    arena.at[row].set(jnp.nan)
                    for arena in dec.pool.kv[name][held:])
        rows = [first]
        for k in range(3):
            tokens = np.zeros(3, np.int32)
            tables = np.zeros((3, dec.max_blocks_per_request), np.int32)
            lens = np.zeros(3, np.int32)
            tokens[0], lens[0], tables[0] = int(rows[-1].argmax()), 11 + k, ta
            tokens[1], lens[1], tables[1] = 1, 9 + k, tb
            out = dec.decode(tokens, tables, lens)
            rows.append(out[0])
            assert np.isnan(out[1]).any() == poison
        return np.stack(rows)

    clean, poisoned = run(False), run(True)
    assert np.isfinite(poisoned).all()
    assert np.array_equal(clean, poisoned)


def test_the_spread_lowers_to_a_take_and_no_scatter():
    """What the form is for: no ``scatter`` (which the TPU's compiler
    runs as a sequential loop over the slots at rows this wide), no loop
    and no product: a gather of the arena's rows."""
    text = jax.jit(rows_ops.spread_rows).lower(
        jnp.zeros((5, 384), jnp.bfloat16), jnp.zeros((4,), jnp.int32),
        jnp.zeros((4, 384), jnp.bfloat16)).as_text()
    assert "scatter" not in text and "while" not in text
    assert "dot_general" not in text and "gather" in text


# ---- a request's convolution tail, one object whichever kind holds it -----------

@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("channels", [128, 96])     # whole lane tiles, and not
def test_a_conv_tail_takes_slides_puts_and_leaves_what_the_lines_did(
        channels, dtype):
    """``ConvTail`` alone, against the lines the three kinds each held:
    its two shapes; ``take`` (the slots' rows cut into taps, zeros for a
    first chunk and nothing else); ``behind`` and ``slide`` (the window
    less its oldest position over the slots' rows: a scatter's bits, row
    0 and the rows nobody names as they were); ``put`` (a prompt's tail
    over its row, in the arena's dtype); ``left`` (the taps that end at a
    chunk's TRUE length, whatever lies behind it)."""
    from flexflow_tpu.serving.cache_entry import ConvTail

    tail = ConvTail(3, channels)
    assert tail.paged(7, dtype).shape == (7, 3 * channels)
    assert tail.dense(2, dtype).shape == (2, 3, channels)
    assert tail.paged(7, dtype).dtype == tail.dense(2, dtype).dtype == dtype
    rng = np.random.default_rng(channels)
    arena = jnp.asarray(rng.normal(size=(7, 3 * channels)), dtype)
    rows = jnp.asarray([4, 0, 2, 6], jnp.int32)
    taken = tail.take(arena, rows)
    np.testing.assert_array_equal(
        _bits(taken), _bits(arena[rows].reshape(4, 3, channels)))
    later = jnp.asarray([True, False, False, True])
    first = tail.take(arena, rows, later)
    np.testing.assert_array_equal(_bits(first)[[0, 3]],
                                  _bits(taken)[[0, 3]])
    assert not np.asarray(first.astype(jnp.float32))[[1, 2]].any()
    new = jnp.asarray(rng.normal(size=(4, 1, channels)), jnp.float32)
    window = tail.behind(taken, new)
    assert window.shape == (4, 4, channels) and window.dtype == arena.dtype
    slid = jax.jit(tail.slide, donate_argnums=0)(jnp.array(arena), rows,
                                                  window)
    want = _bits(arena.at[rows].set(window[:, 1:].reshape(4, -1)))
    want[0] = _bits(arena)[0]
    np.testing.assert_array_equal(_bits(slid), want)
    np.testing.assert_array_equal(_bits(slid)[[1, 3, 5]],
                                  _bits(arena)[[1, 3, 5]])
    prompt = jnp.asarray(rng.normal(size=(2, 3, channels)), jnp.float32)
    put = tail.put(arena, jnp.asarray([5, 1]), prompt)
    assert put.dtype == arena.dtype
    np.testing.assert_array_equal(
        _bits(put)[[5, 1]], _bits(prompt.astype(dtype).reshape(2, -1)))
    np.testing.assert_array_equal(_bits(put)[[0, 2, 3, 4, 6]],
                                  _bits(arena)[[0, 2, 3, 4, 6]])
    chunk = jnp.asarray(rng.normal(size=(2, 3 + 8, channels)), dtype)
    left = tail.left(chunk, jnp.asarray([8, 5]))
    np.testing.assert_array_equal(_bits(left[0]), _bits(chunk[0, 8:11]))
    np.testing.assert_array_equal(_bits(left[1]), _bits(chunk[1, 5:8]))
    assert tail.path(dtype) == "rows"                 # the CPU: no kernel


# ---- a delta-rule op's tails, flat on the lanes in arena order ---------------

_WIDTHS = {128: (4, 8, 16), 384: (4, 32, 32), 96: (4, 8, 8)}  # channels: H, d_k, d_v


def _delta_op(channels, dtype="float32"):
    """A gated-delta-rule op ``channels`` wide, its kind, drawn weights."""
    from flexflow_tpu.ffconst import DataType

    h, dk, dv = _WIDTHS[channels]
    ff = FFModel(FFConfig(batch_size=6, ledger="off",
                          computation_mode=CompMode.INFERENCE))
    x = ff.create_tensor((6, 1, 24), DataType.FLOAT, name="x")
    ff.gated_delta_net(x, num_heads=h, key_dim=dk, value_dim=dv, name="gdn")
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    op = [o for o in ff.compiled.ops if o.name == "gdn"][0]
    assert op.channels == channels
    rng = np.random.default_rng(channels)
    w = {k: jnp.asarray(rng.normal(size=v.shape) * (0.3 if v.ndim > 1
                                                    else 1.0), dtype)
         for k, v in ff.compiled.params["gdn"].items()}
    return op, StateEntry.for_op(op, None, 32), w


def _tails_case(channels, dtype, rows):
    """An arena of 7 rows, six slots' inputs (slot 3's NaN), row 0 sevens."""
    rng = np.random.default_rng(channels + len(rows))
    tails = jnp.asarray(rng.normal(size=(7, 3 * channels)), dtype)
    tails = tails.at[0].set(7.0)
    inputs = jnp.asarray(rng.normal(size=(len(rows), channels)), dtype)
    return tails, inputs.at[3].set(jnp.nan), jnp.asarray(rows, jnp.int32)


def _tails_in_slot_order(op, w, tails, rows, inputs):
    """The lines a step held up to PR 58 and holds where the kernel
    refuses: the slots' rows cut into taps, ``convolve`` over the window
    ``(n, taps, channels)``, ``spread_rows`` on the way back."""
    n, c = inputs.shape
    window = jnp.concatenate([tails[rows].reshape(n, 3, c),
                              inputs[:, None]], axis=1)
    return (op.convolve(w, window),
            rows_ops.spread_rows(tails, rows,
                                     window[:, 1:].reshape(n, -1)))


@pytest.mark.parametrize("case", list(_ROWS))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("channels", [128, 384])
def test_tails_stepped_in_arena_order_are_the_spreads_bit_for_bit(
        monkeypatch, channels, dtype, case):
    """``ConvTail.step_arena`` (the ``tails_step`` kernel, interpreted)
    against the slot-order lines it replaced: the whole arena BIT for bit
    (every live row's new tail moved into place, the rows nobody names
    and row 0 as they were), slot 3's NaN in its own row and nowhere
    else, and the live slots' convolved rows the same float32 products
    in the same order."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    op, kind, w = _delta_op(channels, dtype)
    assert kind.tails_path(dtype) == "kernel"
    tails, inputs, rows = _tails_case(channels, dtype, _ROWS[case])
    u, got = jax.jit(kind.conv_tail.step_arena, donate_argnums=0)(
        jnp.array(tails), rows, inputs, w["conv"])
    u_ref, want = jax.jit(_tails_in_slot_order, static_argnums=0)(
        op, w, tails, rows, inputs)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    np.testing.assert_array_equal(_bits(got)[0], _bits(tails)[0])
    live = np.asarray(rows) != 0
    assert u.shape == u_ref.shape == (6, 1, channels)
    assert u.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(u)[live], np.asarray(u_ref)[live],
                               rtol=1e-6, atol=1e-6)
    clean = live & (np.arange(6) != 3)
    assert np.isfinite(np.asarray(u)[clean]).all()
    # an idle slot convolves the null row's taps behind zeros, whatever
    # any slot carries
    assert np.isfinite(np.asarray(u)[~live]).all()
    poisoned = {int(rows[3])} - {0}
    assert np.isfinite(np.asarray(got.astype(jnp.float32))[
        sorted(set(range(7)) - poisoned)]).all()


@pytest.mark.parametrize("channels, mode, path", [
    (128, "interpret", "kernel"), (384, "interpret", "kernel"),
    (96, "interpret", "rows"), (128, "off", "rows")])
def test_a_states_step_takes_its_tails_by_the_width_and_counts_it(
        monkeypatch, channels, mode, path):
    """``StateEntry.step`` whole: where a tap is whole lane tiles and the
    kernels run, the arena-order pass, counted ``state_tails.path.kernel``,
    its program with no gather or scatter of ``tail * channels``-wide rows
    and no ``(n, taps, channels)`` or ``(n, tail, channels)`` array; a
    width of 96, or the kernels off, takes the old lines (``.rows``),
    which hold all three. Both leave the outputs and the arenas the old
    lines leave."""
    from flexflow_tpu.obs.metrics import metrics_registry
    from flexflow_tpu.serving.kv_cache import Addresses

    op, kind, w = _delta_op(channels)
    tails, _, rows = _tails_case(channels, "float32", _ROWS["several_idle"])
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(6, 1, 24)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(7, op.key_dim,
                                         op.num_heads * op.value_dim)),
                        jnp.float32)
    addr = Addresses(jnp.zeros((6, 2), jnp.int32), rows)

    def step(state, tails):
        return kind.step(op, w, x, None, (state, tails), addr, None)

    # (a function of its own: a second trace of ``step`` would be the first)
    y_ref, (state_ref, tails_ref) = jax.jit(lambda *a: step(*a))(state, tails)
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
    assert kind.tails_path(tails.dtype) == kind.stats()["tails_path"] == path
    reg = metrics_registry()
    before = {p: reg.counter(f"state_tails.path.{p}").value
              for p in ("kernel", "rows")}
    lowered = jax.jit(step).lower(state, tails)
    assert {p for p, v in before.items()
            if reg.counter(f"state_tails.path.{p}").value > v} == {path}
    text = lowered.as_text()
    wide = [line for line in text.splitlines()
            if ("gather" in line or "scatter" in line)
            and f"x{3 * channels}x" in line]
    cut = [f"x{taps}x{channels}x" in text for taps in (3, 4)]
    assert (not wide and not any(cut)) if path == "kernel" \
        else (wide and all(cut))
    y, (new_state, new_tails) = lowered.compile()(state, tails)
    np.testing.assert_array_equal(_bits(new_tails), _bits(tails_ref))
    live = np.asarray(rows) != 0
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(y_ref)[live],
                               rtol=1e-5, atol=1e-6)
    held = np.asarray(rows)[live]
    np.testing.assert_allclose(np.asarray(new_state)[held],
                               np.asarray(state_ref)[held],
                               rtol=1e-5, atol=1e-6)


def test_a_latent_row_has_no_int8_form():
    assert LatentEntry(24).int8_form is None
    assert PairEntry(4, 8).int8_form == Int8PairEntry(4, 8)
    with pytest.raises(ValueError, match="attn: a latent cache entry has "
                                         "no int8 form"):
        PagedKVPool({"attn": LatentEntry(24)}, num_blocks=4, block_size=8,
                    max_blocks_per_request=2, kv_dtype="int8")


# ---- (c) limits reach the public path ----------------------------------------

def test_spec_k_over_a_latent_model_is_refused_at_construction(latent_lm,
                                                               gpt):
    dec = PagedDecoder(latent_lm, 32, decode_slots=2, block_size=8)
    assert {type(k) for k in dec.pool.kinds.values()} == {LatentEntry}
    dec.check_window(1)
    with pytest.raises(
            ValueError,
            match=r"speculative verify over a latent cache entry is not "
                  r"built \(\w+ and 1 more\): serve this model with "
                  r"spec_k=0"):
        GenerationInstance(latent_lm, decode_slots=2, block_size=8,
                           max_length=32, spec_k=2, draft_ff=latent_lm)
    PagedDecoder(gpt, 32, decode_slots=2, block_size=8).check_window(5)


# ---- (c) the selecting kind and the decay's state ------------------------------

GEOM = dict(kernel=8, stride=4, block=16, window=20, dense_len=64,
            init_blocks=1, topk=4)


@pytest.fixture(scope="module")
def sparse_lm():
    from flexflow_tpu.models import (SparseHybridConfig,
                                     build_sparse_hybrid_lm)

    return _model(build_sparse_hybrid_lm, 96, SparseHybridConfig(
        vocab_size=V, hidden_size=32, num_heads=4, num_kv_heads=2,
        head_dim=8, linear_heads=4, linear_head_dim=8, mlp_width=64,
        selection=GEOM))


@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16"])
def test_the_sparse_and_decay_kinds_answer_for_their_arenas_and_limits(
        kv_dtype):
    """One pool, three arenas a sparse op (keys and values head-major in a
    block, a pooled key every stride tokens) and one a decay op (float32
    whatever ``kv_dtype`` says): shapes, bytes a token and a request,
    names, limits, and what a step reads."""
    from flexflow_tpu.ops.block_sparse_attention import Selection
    from flexflow_tpu.serving.cache_entry import DecayStateEntry, SparseEntry

    sparse = SparseEntry(2, 8, Selection(**GEOM))
    decay = DecayStateEntry(4, 8)
    pool = PagedKVPool({"d0": decay, "a": sparse, "d1": decay},
                       num_blocks=NB, block_size=16,
                       max_blocks_per_request=6, kv_dtype=kv_dtype,
                       num_rows=4)
    store = jnp.bfloat16 if kv_dtype == "bfloat16" else jnp.float32
    assert [(a.shape, a.dtype) for a in pool.kv["a"]] == [
        ((NB, 2, 16, 8), store), ((NB, 2, 16, 8), store),
        ((NB * 4, 16), store)]
    assert [(a.shape, a.dtype) for a in pool.kv["d0"]] == [
        ((4, 4, 8, 8), jnp.float32)]
    held = sum(a.nbytes for entry in pool.kv.values() for a in entry)
    item = jnp.dtype(store).itemsize
    assert sparse.token_bytes(store) == (2 * 16 + 16 // 4) * item
    assert decay.request_bytes(store) == 4 * 8 * 8 * 4 and decay.keeps_row
    assert pool.memory_bytes() == held == serving_kv_pool_bytes(
        pool.specs, NB, 16, kv_dtype, num_rows=4)
    st = pool.stats()
    assert st["entry"] == {"decay_state": 2, "sparse": 1}
    assert st["kernels_per_block"] == 4 and st["state_dtype"] == "float32"
    assert st["state"]["row_bytes"] == 2 * 4 * 8 * 8 * 4
    for kind in (sparse, decay):
        assert kind.max_window == 1 and kind.int8_form is None
        assert kind.chunked
    # below dense_len every live block, past it topk of them
    assert [sparse.blocks_read(n) for n in (0, 15, 16, 63, 64, 200)] == [
        1, 1, 2, 4, 4, 4]
    assert decay.blocks_read(200) is None and PairEntry(4, 8).blocks_read(
        200) is None
    # whole kernels over n keys: (n - 8) // 4 + 1
    assert [sparse.side_rows(n) for n in (0, 7, 8, 11, 12, 100)] == [
        0, 0, 1, 1, 2, 24]
    assert [(a.shape, a.dtype) for a in sparse.dense_shapes(2, 40, store)] \
        == [((2, 48, 2, 8), store)] * 2
    with pytest.raises(ValueError, match="a: a sparse cache entry has no "
                                         "int8 form"):
        PagedKVPool({"a": sparse}, num_blocks=4, block_size=16,
                    max_blocks_per_request=2, kv_dtype="int8")
    with pytest.raises(ValueError, match="block_size 8 has to be that"):
        PagedKVPool({"a": sparse}, num_blocks=4, block_size=8,
                    max_blocks_per_request=2)


@pytest.mark.parametrize("op_type,layer", [
    (OpType.BLOCK_SPARSE_ATTENTION, "block1_mixer"),
    (OpType.LIGHTNING_ATTENTION, "block0_mixer")])
def test_the_new_kinds_are_a_class_and_a_line_in_kinds(sparse_lm, monkeypatch,
                                                       op_type, layer):
    """Taken out of ``KINDS`` the op is refused by name; a subclass
    defined here and put in its place serves through the unedited decoder
    and scheduler, in chunks, what the package's kind serves."""
    own = cache_entry.KINDS[op_type]
    rng = np.random.default_rng(11)
    reqs = [(rng.integers(0, V, (n,)).astype(np.int32), 6)
            for n in (70, 20, 90)]
    gen = Generator(sparse_lm, max_length=96, batch_size=1)
    want = [gen.generate(p[None, :], m)[0] for p, m in reqs]

    @dataclasses.dataclass(frozen=True)
    class _Renamed(own.__self__):
        name = "renamed"

    monkeypatch.setitem(cache_entry.KINDS, op_type, _Renamed.for_op)
    sched = ContinuousBatchingScheduler(sparse_lm, max_length=96,
                                        decode_slots=2, block_size=16,
                                        prefill_chunk=32)
    try:
        got = [f.result(timeout=300)
               for f in [sched.submit(p, m) for p, m in reqs]]
        stats = sched.stats()
    finally:
        sched.stop()
    for out, ref in zip(got, want):
        np.testing.assert_array_equal(out, ref)
    assert isinstance(sched.decoder.pool.kinds[layer], _Renamed)
    assert stats["kv"]["entry"]["renamed"] >= 1
    monkeypatch.delitem(cache_entry.KINDS, op_type)
    with pytest.raises(ValueError, match=rf"{layer}.*no cache entry kind "
                                         rf".*{op_type.name}"):
        PagedDecoder(sparse_lm, 96, decode_slots=2, block_size=16)
