"""Paged KV cache pool (serving/kv_cache.py): allocator invariants,
admission shedding, and observability."""

import numpy as np
import pytest

import jax.numpy as jnp

from flexflow_tpu.obs.metrics import metrics_registry
from flexflow_tpu.serving.cache_entry import PairEntry
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
