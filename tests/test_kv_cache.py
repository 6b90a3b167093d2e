"""Paged KV cache pool (serving/kv_cache.py): allocator invariants,
admission shedding, and observability."""

import numpy as np
import pytest

import jax.numpy as jnp

from flexflow_tpu.obs.metrics import metrics_registry
from flexflow_tpu.serving.cache_entry import PairEntry, StateEntry
from flexflow_tpu.serving.errors import KVPoolExhausted, ShedError
from flexflow_tpu.serving.kv_cache import (NULL_BLOCK, PagedKVPool,
                                           run_groups)


def _pool(num_blocks=9, block_size=4, max_blocks=4, **kw):
    return PagedKVPool({"attn0": PairEntry(2, 8), "attn1": PairEntry(2, 8)},
                       num_blocks=num_blocks, block_size=block_size,
                       max_blocks_per_request=max_blocks, **kw)


def test_pool_geometry_and_arenas():
    p = _pool()
    assert p.capacity_blocks == 8  # block 0 reserved
    assert set(p.kv) == {"attn0", "attn1"}
    k, v = p.kv["attn0"]
    # one row a token, heads side by side: (blocks, block_size, H*D)
    assert k.shape == (9, 4, 16) and v.shape == (9, 4, 16)
    assert k.dtype == jnp.float32
    # memory math: 2 arenas/op x 2 ops x 9*4 slots x 2*8 x 4B
    assert p.memory_bytes() == 2 * 2 * 9 * 4 * 2 * 8 * 4
    assert p.blocks_for(1) == 1
    assert p.blocks_for(4) == 1
    assert p.blocks_for(5) == 2
    assert p.blocks_for(16) == 4


def test_pool_validation():
    with pytest.raises(ValueError, match="null block"):
        _pool(num_blocks=1)
    with pytest.raises(ValueError, match="block_size"):
        _pool(block_size=0)
    with pytest.raises(ValueError, match="max_blocks_per_request"):
        _pool(max_blocks=0)


def test_admit_free_round_trip_and_null_padding():
    p = _pool()
    t = p.try_admit(6)  # 2 blocks
    assert t is not None and t.shape == (4,)
    used = [int(b) for b in t if b != NULL_BLOCK]
    assert len(used) == 2
    assert NULL_BLOCK not in used  # the null block is never allocated
    assert list(t[2:]) == [NULL_BLOCK, NULL_BLOCK]  # padded tail
    assert p.in_use() == 2
    p.free(t)
    assert p.in_use() == 0


def test_admit_returns_none_when_full_then_recovers():
    p = _pool()
    t1 = p.try_admit(16)  # 4 blocks
    t2 = p.try_admit(16)  # 4 more — pool now full
    assert p.in_use() == 8
    assert p.try_admit(4) is None  # transient: wait, don't shed
    p.free(t1)
    t3 = p.try_admit(4)
    assert t3 is not None
    p.free(t2)
    p.free(t3)


def test_impossible_worst_case_sheds():
    p = _pool(num_blocks=5, max_blocks=8)  # capacity 4 < 5-block ask
    with pytest.raises(KVPoolExhausted, match="exceeds the whole pool"):
        p.try_admit(20)
    # a KVPoolExhausted IS a ShedError (admission-control taxonomy)
    with pytest.raises(ShedError):
        p.try_admit(20)
    # and a request over the per-request table width sheds too
    p2 = _pool(num_blocks=20, max_blocks=2)
    with pytest.raises(KVPoolExhausted, match="max_blocks_per_request"):
        p2.try_admit(12)


def test_high_water_and_gauge_track_occupancy():
    p = _pool()
    g = metrics_registry().gauge("serving.kv_blocks_in_use")
    t1 = p.try_admit(16)
    assert g.value == 4
    t2 = p.try_admit(8)
    assert g.value == 6
    assert p.high_water == 6
    p.free(t1)
    p.free(t2)
    assert g.value == 0
    assert p.high_water == 6  # high water survives frees
    assert p.stats()["high_water"] == 6
    assert p.stats()["in_use"] == 0


def test_double_free_is_loud():
    p = _pool()
    t = p.try_admit(16)
    p.free(t)
    with pytest.raises(RuntimeError, match="double free"):
        p.free(t)


# ---- tables made of ascending stretches ------------------------------------------

@pytest.mark.parametrize("frees, asks, want", [
    # the same need again: the same table
    ([0], [16], [[1, 2, 3, 4]]),
    # less, then the rest: one freed table's stretch carried on
    ([0], [8, 8], [[1, 2], [3, 4]]),
    # across a seam: the table freed last first, then the one before it
    ([0, 1], [28], [[5, 6, 7, 1, 2, 3, 4]]),
    ([1, 0], [20], [[1, 2, 3, 4, 5]]),
])
def test_a_freed_table_comes_out_ascending_again(frees, asks, want):
    """Blocks go back so that the next tables pop a freed table's blocks
    in the order the table had them: ascending stretches, seams between
    them, never a stretch backwards."""
    p = _pool(max_blocks=8)
    tables = [p.try_admit(16), p.try_admit(12)]
    assert list(tables[0][:4]) == [1, 2, 3, 4]
    assert list(tables[1][:3]) == [5, 6, 7]
    for i in frees:
        p.free(tables[i])
    for ask, blocks in zip(asks, want):
        t = p.try_admit(ask)
        assert [int(b) for b in t if b != NULL_BLOCK] == blocks
    # pairs of (k, v): a copy a block, every entry a group of its own
    handed = 7 + sum(len(b) for b in want)
    assert p.stats()["fetch_runs"] == {"groups": handed, "run_blocks": 1,
                                       "groups_run": handed}


def _latent_pool(**kw):
    from flexflow_tpu.serving.cache_entry import LatentEntry

    # rows of 576 in 640 lanes, blocks of 16, bfloat16: the reasoning
    # cell's 20 KB a block, four of them a copy
    return PagedKVPool({"attn": LatentEntry(576)}, num_blocks=65,
                       block_size=16, max_blocks_per_request=32,
                       kv_dtype="bfloat16", **kw)


def test_fetch_runs_counts_what_a_table_holds():
    """Whole groups of ``run_blocks`` entries and those that are
    neighbours ascending, of a hand-made table and of the allocator's
    own: a seam between two freed tables' stretches breaks the group it
    falls in and no other."""
    p = _latent_pool()
    assert p.stats()["fetch_runs"] == {"groups": 0, "groups_run": 0,
                                       "run_blocks": 4}
    assert run_groups([1, 2, 3, 4,  9, 10, 11, 12,  5, 6, 8, 7,
                       20, 19, 18, 17,  13, 14, 15, 17,  30, 31, 32],
                      4) == (5, 2)
    assert run_groups([3, 2, 1], 1) == (3, 3) and run_groups([1, 2], 4) \
        == (0, 0)
    t1 = p.try_admit(10 * 16)        # two groups, and 9 10 of a third
    assert list(t1[:10]) == list(range(1, 11))
    t2 = p.try_admit(6 * 16)         # a group, and 15 16
    assert list(t2[:6]) == list(range(11, 17))
    assert p.in_use() == 16 and p.stats()["in_use"] == 16
    s = p.stats()["fetch_runs"]
    assert (s["groups"], s["groups_run"]) == (2 + 1, 2 + 1)
    p.free(t1)
    p.free(t2)
    assert p.in_use() == 0
    # the table freed last first, each in the order it had: the seam
    # 16 | 1 lies in the second group, the seam 10 | 17 in t4's only one
    t3 = p.try_admit(13 * 16)
    assert list(t3[:13]) == [11, 12, 13, 14, 15, 16, 1, 2, 3, 4, 5, 6, 7]
    t4 = p.try_admit(7 * 16)
    assert list(t4[:7]) == [8, 9, 10, 17, 18, 19, 20]
    s = p.stats()["fetch_runs"]
    assert (s["groups"], s["groups_run"]) == (3 + 3 + 1, 3 + 2 + 0)


def _second_generation(pool, rng, free):
    """The reasoning mix in small (``benchmark/traffic/
    serve-reasoning.json``: prompts of 512-1,024 tokens, answers of
    1,024-3,072, tables reserved whole, a closed loop with a client a
    slot, two jobs a slot): the share of whole groups that are runs in
    the tables admitted after the first free."""
    import heapq

    slots = (pool.num_blocks - 1) // pool.max_blocks_per_request
    prompts = rng.integers(512, 1025, size=2 * slots)
    answers = rng.integers(1024, 3073, size=2 * slots)
    held, done = {}, []
    for job in range(slots):
        held[job] = pool.try_admit(int(prompts[job] + answers[job]))
        heapq.heappush(done, (int(answers[job]), job))
    first = dict(pool.stats()["fetch_runs"])
    assert first["groups_run"] == first["groups"] > 0
    for job in range(slots, 2 * slots):
        now, finished = heapq.heappop(done)
        free(pool, held.pop(finished))
        held[job] = pool.try_admit(int(prompts[job] + answers[job]))
        heapq.heappush(done, (now + int(answers[job]), job))
    last = pool.stats()["fetch_runs"]
    return ((last["groups_run"] - first["groups_run"])
            / (last["groups"] - first["groups"]))


def test_the_second_generation_of_tables_is_runs_still():
    """What the reversed free is for: the tables a slot's second request
    is handed are ascending stretches of the tables freed before it, and
    all but the groups at their seams come by one copy. Handed back in
    table order, as the pool did until PR 56 (a table freed backwards
    here), the free list pops them descending and next to no group is a
    run."""
    def pool():
        from flexflow_tpu.serving.cache_entry import LatentEntry

        return PagedKVPool({"attn": LatentEntry(576)},
                           num_blocks=8 * 256 + 1, block_size=16,
                           max_blocks_per_request=256, kv_dtype="bfloat16")

    as_it_is = _second_generation(pool(), np.random.default_rng(56),
                                  lambda p, t: p.free(t))
    table_order = _second_generation(pool(), np.random.default_rng(56),
                                     lambda p, t: p.free(t[::-1].copy()))
    assert as_it_is > 0.9, as_it_is
    assert table_order < 0.5, table_order


def test_a_full_pool_and_exhaustion_are_as_before():
    p = _latent_pool()
    a, b, c = (p.try_admit(6 * 16) for _ in range(3))
    assert [list(t[:6]) for t in (a, b, c)] == [
        list(range(1, 7)), list(range(7, 13)), list(range(13, 19))]
    with pytest.raises(KVPoolExhausted, match="max_blocks_per_request"):
        p.try_admit(33 * 16)
    big = p.try_admit(32 * 16)
    assert big is not None and list(big[:32]) == list(range(19, 51)) \
        and p.in_use() == 50
    assert p.try_admit(15 * 16) is None          # 14 left: wait
    last = p.try_admit(14 * 16)
    assert list(last[:14]) == list(range(51, 65))
    assert p.in_use() == p.capacity_blocks and p.try_admit(16) is None
    s = p.stats()["fetch_runs"]
    # b and c begin off a multiple of four in the arena: a run is
    # neighbours ascending from wherever the table's group begins
    assert (s["groups"], s["groups_run"]) == (3 + 8 + 3, 3 + 8 + 3)
    for table in (a, b, c, big, last):
        p.free(table)
    assert p.in_use() == 0
    with pytest.raises(RuntimeError, match="double free"):
        p.free(a)
    assert p.stats()["fetch_runs"]["run_blocks"] == 4


@pytest.mark.parametrize("before, after, want", [
    # every group a run: what one copy brings is the whole group
    ({"groups": 10, "groups_run": 10}, {"groups": 30, "groups_run": 30}, 4.0),
    # none: a copy a block
    ({"groups": 0, "groups_run": 0}, {"groups": 8, "groups_run": 0}, 1.0),
    # 18 of 20 groups: 80 blocks by 18 + 8 copies
    ({"groups": 5, "groups_run": 5}, {"groups": 25, "groups_run": 23},
     80 / 26),
    # a window that admitted nothing, and a program without the counter
    ({"groups": 5, "groups_run": 5}, {"groups": 5, "groups_run": 5}, None),
    (None, None, None),
])
def test_blocks_per_fetch_reads_the_windows_tables(before, after, want):
    """The benchmark's reader over the counter's deltas (the one the
    reasoning cell reports as ``kv_blocks_per_fetch``)."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "kv_blocks_per_fetch", os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "layer_metrics",
            "kv_blocks_per_fetch.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    kv = lambda runs: {"kv": dict(  # noqa: E731
        {"block_size": 16},
        **({"fetch_runs": dict(runs, run_blocks=4)} if runs else {}))}
    got = reader.read({"facts": {"stats0": kv(before), "stats1": kv(after)}})
    assert got == want if want is None else got == pytest.approx(want)
    assert reader.read({"facts": {}}) is None


# ---- per-request rows beside the blocks ------------------------------------------

def _mixed(num_rows=3, **kw):
    return PagedKVPool({"attn": PairEntry(2, 8),
                        "mix": StateEntry(2, 8, 64, 3, 160)},
                       num_blocks=9, block_size=4, max_blocks_per_request=4,
                       num_rows=num_rows, **kw)


def test_admission_reserves_blocks_and_a_row_or_neither():
    p = _mixed()                         # two rows beside the null row
    a, b = p.try_admit(8), p.try_admit(4)
    assert sorted(p.rows_of(np.stack([a, b])).tolist()) == [1, 2]
    assert p.stats()["state"]["in_use"] == 2
    used = p.in_use()
    # blocks are left (5 of 8) and no row is: nothing is taken
    assert p.try_admit(4) is None
    assert p.in_use() == used and p.stats()["state"]["in_use"] == 2
    p.free(a)
    assert p.in_use() == used - 2 and p.stats()["state"]["in_use"] == 1
    # a row is left and too few blocks are: nothing is taken either
    c = p.try_admit(16)
    assert p.in_use() == 1 + 4
    assert p.try_admit(16) is None
    assert p.stats()["state"]["in_use"] == 2
    p.free(b)
    p.free(c)
    st = p.stats()
    assert st["in_use"] == 0 and st["state"]["in_use"] == 0
    assert st["state"]["high_water"] == 2 and st["state"]["rows"] == 3


def test_an_idle_table_names_the_null_row_and_a_freed_one_too():
    p = _mixed()
    t = p.try_admit(8)
    idle = np.zeros_like(t)
    assert p.rows_of(np.stack([idle, t, idle])).tolist() == [0, 1, 0]
    p.free(t)
    assert p.rows_of(t[None]).tolist() == [0]
    # a pool without per-request kinds has no rows to name
    assert _pool().rows_of(t[None]) is None and _pool().num_rows == 0
    assert "state" not in _pool().stats()


def test_double_free_of_a_row_is_loud_and_takes_nothing():
    p = _mixed()
    t = p.try_admit(8)
    p.free(t)
    with pytest.raises(RuntimeError, match="double free: the request of "
                                           "block .* holds no state row"):
        p.free(t)
    assert p.in_use() == 0 and p.stats()["state"]["in_use"] == 0
    # both rows can still be taken, once each
    assert sorted(p.rows_of(np.stack([p.try_admit(4),
                                      p.try_admit(4)])).tolist()) == [1, 2]


def test_pool_bytes_has_a_term_a_token_and_a_term_a_request():
    from flexflow_tpu.serving.kv_cache import pool_bytes

    p = _mixed(num_rows=5, kv_dtype="bfloat16")
    per_token = 2 * 2 * 8 * 2
    per_row = 8 * 2 * 64 * 4 + 3 * 160 * 2     # the state stays float32
    assert p.memory_bytes() == 9 * 4 * per_token + 5 * per_row
    assert pool_bytes(p.specs, 9, 4, "bfloat16", num_rows=5) \
        == p.memory_bytes()
    assert pool_bytes(p.specs, 9, 4, "bfloat16") == 9 * 4 * per_token


# ------------------------------------------------ the books of what steps read
def test_the_scheduler_names_no_entry_kind():
    """What a step reads of each kind of entry is the pool's to count:
    the serving loop, two layers above the kinds, names none of their
    words, so a new kind costs the scheduler nothing."""
    import inspect

    from flexflow_tpu.serving import scheduler

    src = inspect.getsource(scheduler)
    for word in ("blocks_read", "rows_read", "side_rows", "keeps_row",
                 "_selecting", "_windowed", "_state_ops", "pool.kinds"):
        assert word not in src, word


@pytest.mark.parametrize("model", ["gpt", "hybrid", "sparse_hybrid",
                                   "latent_moe", "nemotron_h", "trinity"])
def test_the_pool_keeps_the_books_of_what_the_steps_read(model):
    """Each serving family of the zoo through a scripted session (two
    prompts seated, four decode passes, nothing retired): ``stats()
    ["kv"]``, which is ``PagedKVPool.stats(lengths)`` and the decoder's
    words, holds every book to the lengths' arithmetic, each present
    exactly where the pool has such a kind; ``stats()`` without lengths
    holds none of them."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.ffconst import CompMode
    from flexflow_tpu.models import zoo_smoke_builders
    from flexflow_tpu.serving import cache_entry
    from flexflow_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                                GenerationRequest)

    ff = FFModel(FFConfig(batch_size=2, seed=0, ledger="off",
                          search_cache="off",
                          computation_mode=CompMode.INFERENCE))
    zoo_smoke_builders()[model](ff, 2)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    bs = 4 if model == "sparse_hybrid" else 8
    sched = ContinuousBatchingScheduler(
        ff, max_length=48, decode_slots=3, block_size=bs,
        prefill_buckets=[16])
    pool = sched.decoder.pool
    prompts, passes = (5, 14), 4      # lengths 5..8 and 14..17 at the steps
    for slot, n in enumerate(prompts):
        req = GenerationRequest(slot, np.arange(1, n + 1, dtype=np.int32),
                                20, 0.0, 0, None, None)
        req.table = pool.try_admit(n + 20)
        assert sched._prefill_group([(slot, req)], 16) == 1
    for _ in range(passes):
        sched._decode_once()
    kv = sched.stats()["kv"]
    sched._read_steps(keep=0)
    sched.stop()

    stepped = [n + k for n in prompts for k in range(passes)]
    held = [n + passes for n in prompts]
    kinds = list(pool.kinds.values())
    assert kv["blocks_read"] == sum((x + bs) // bs for x in stepped)
    assert kv["blocks_in_tables"] == len(stepped) * (48 // bs)
    plain = pool.stats()
    assert not {"blocks_read", "blocks_in_tables", "selected",
                "kernel_rows"} & set(plain)
    assert kv["in_use"] == plain["in_use"] == sum(
        pool.blocks_for(n + 20) for n in prompts)

    state_ops = sum(k.keeps_row for k in kinds)
    assert ("state" in kv) == bool(state_ops) == (model not in (
        "gpt", "latent_moe"))
    if state_ops:
        assert kv["state"]["rows_stepped"] == len(stepped) * state_ops
        assert kv["state"]["in_use"] == 2
        assert "prefill_path" in kv["state"]
        assert set(plain["state"]) == {"rows", "in_use", "high_water",
                                       "row_bytes"}

    sparse = [k for k in kinds if isinstance(k, cache_entry.SparseEntry)]
    assert ("selected" in kv) == ("kernel_rows" in kv) == bool(sparse) \
        == (model == "sparse_hybrid")
    if sparse:      # dense_len 16, topk 3 of blocks of 4; kernels 4 by 2
        assert kv["selected"] == {
            "blocks_read": sum(x // 4 + 1 if x < 16 else 3 for x in stepped),
            "blocks_live": kv["blocks_read"]}
        assert kv["kernel_rows"] == sum((x - 4 + 2) // 2 for x in held) > 0

    windowed = [k for k in kinds if isinstance(k, cache_entry.WindowEntry)]
    assert bool(windowed) == (model == "trinity")
    if windowed:    # the books' dict in place of the kind's own word
        assert plain["window"] == 16
        assert kv["window"] == {
            "rows_read": sum(min(x + 1, 16) for x in stepped),
            "rows_full": sum(x + 1 for x in stepped),
            "rows_reserved": 16 * len(stepped),
            "rows": 16, "ops": len(windowed),
            "rows_held": sum(min(x, 16) for x in held)}
        assert pool.chunk_keys(8, 16) == (
            sum(range(9, 25)), sum(min(p, 16) for p in range(9, 25)))
    else:
        assert "window" not in kv
        assert pool.chunk_keys(8, 16) == (sum(range(9, 25)), 0)
