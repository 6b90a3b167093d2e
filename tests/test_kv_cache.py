"""Paged KV cache pool (serving/kv_cache.py): allocator invariants,
admission shedding, and observability."""

import numpy as np
import pytest

import jax.numpy as jnp

from flexflow_tpu.obs.metrics import metrics_registry
from flexflow_tpu.serving.cache_entry import PairEntry, StateEntry
from flexflow_tpu.serving.errors import KVPoolExhausted, ShedError
from flexflow_tpu.serving.kv_cache import NULL_BLOCK, PagedKVPool


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
    for word in ("blocks_read", "rows_read", "side_rows", "per_request",
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

    state_ops = sum(k.per_request for k in kinds)
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
