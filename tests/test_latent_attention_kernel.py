"""The latent decode kernel's fetch: a group of table entries that are
neighbours ascending in the arena comes by ONE copy, any other group by a
copy a block, and either way the sums are the gather's
(``LatentEntry.step``'s jnp path). Through the Pallas interpreter; the
compiled kernel at the benchmark cell's shape is in
tests_tpu/test_compiled_kernels.py."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from flexflow_tpu.kernels import latent_attention  # noqa: E402
from flexflow_tpu.serving.kv_cache import NULL_BLOCK  # noqa: E402

HEADS, RANK, ROW, BLOCK, MAX_BLOCKS, PAGES = 16, 96, 128, 16, 24, 8
# an idle slot, whole groups of 4 live, a last live block in the middle
# of a group (6 and 7 blocks live), the table's last row
LENS = np.array([0, 4 * BLOCK - 1, 5 * BLOCK + 3, MAX_BLOCKS * BLOCK - 1,
                 6 * BLOCK + 3], np.int32)


def _tables(order: str, rng) -> np.ndarray:
    """(slots, MAX_BLOCKS) ids from 1 up, each once, slot 0 idle."""
    n = LENS.size
    ids = np.arange(1, n * MAX_BLOCKS + 1, dtype=np.int32)
    if order == "runs":                    # every group a run
        pass
    elif order == "singles":               # no two neighbours ascending
        ids = ids.reshape(-1, 2)[:, ::-1].reshape(-1)
    elif order == "seam":                  # stretches of 5, 6 and 7 blocks
        cuts, at = [], 0
        while at < ids.size:
            cuts.append(ids[at:at + 5 + len(cuts) % 3])
            at += cuts[-1].size
        ids = np.concatenate([cuts[i] for i in rng.permutation(len(cuts))])
    elif order == "descending":            # a freed table popped backwards
        ids = ids.reshape(n, MAX_BLOCKS)[:, ::-1].reshape(-1)
    else:
        raise ValueError(order)
    tables = ids.reshape(n, MAX_BLOCKS).copy()
    tables[LENS == 0] = NULL_BLOCK
    return tables


def _case(order: str, dtype="float32"):
    """Rows where a slot may look, NaN in every block no slot's live
    stretch holds: a copy that reaches past ``live`` poisons the sums."""
    rng = np.random.default_rng(56)
    tables = _tables(order, rng)
    arena = np.full((LENS.size * MAX_BLOCKS + 1, BLOCK, ROW), np.nan,
                    np.float32)
    arena[NULL_BLOCK] = 3.0e4
    for row, length in zip(tables, LENS):
        if length:
            live = row[:(int(length) + BLOCK) // BLOCK]
            arena[live] = rng.normal(size=(live.size, BLOCK, ROW))
    q = rng.normal(size=(LENS.size, HEADS, ROW)).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(arena, dtype),
            jnp.asarray(tables), jnp.asarray(LENS))


def _gather(q, arena, tables, lens, scale):
    n, mb = tables.shape
    view = jnp.nan_to_num(arena[tables].reshape(n, mb * BLOCK, ROW)
                          .astype(jnp.float32))
    s = jnp.einsum("nhr,nlr->nhl", q.astype(jnp.float32), view) * scale
    seen = jnp.arange(mb * BLOCK)[None] <= lens[:, None]
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), -1)
    return np.asarray(jnp.einsum("nhl,nlc->nhc", p, view[..., :RANK]))


@pytest.mark.parametrize("run", [1, 2, 4])
@pytest.mark.parametrize("order", ["runs", "singles", "seam", "descending"])
def test_a_run_of_neighbours_is_one_copy_and_the_same_sums(monkeypatch,
                                                           order, run):
    """Tables that are all runs, all singles, runs broken at seams inside
    a chunk, and a descending stretch, at ``run`` 1, 2 and 4, with slots
    whose last live block falls inside a group (the group straddles
    ``live`` and its dead entries hold NaN): the gather's sums, and to
    the bit the sums of a copy a block."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    q, arena, tables, lens = _case(order)
    assert latent_attention.supported(q.shape, arena.shape, "float32",
                                      MAX_BLOCKS, ROW)
    call = lambda r: np.asarray(latent_attention.latent_attention_decode(  # noqa: E731
        q, arena, tables, lens, scale=0.1, out_width=ROW,
        pages_per_chunk=PAGES, blocks_per_run=r))[..., :RANK]
    got = call(run)
    assert np.isfinite(got).all(), "a copy reached past the live blocks"
    active = LENS > 0
    want = _gather(q, arena, tables, lens, 0.1)
    worst = np.abs(got[active] - want[active]).max()
    assert worst <= 5e-6 * np.abs(want[active]).max(), worst
    if run > 1:
        assert np.array_equal(got, call(1))


@pytest.mark.parametrize("table, cached, want", [
    # two chunks of 8 blocks in groups of 4, every one a run: the first
    # chunk whole, the second as far as it is live, nothing past it
    (list(range(1, 17)), 16 * BLOCK - 1, ["WHOLE", "WHOLE"]),
    (list(range(1, 17)), 13 * BLOCK, ["WHOLE", "TAIL"]),
    (list(range(1, 17)), 3 * BLOCK, ["TAIL", "TAIL"]),
    # a seam inside a live group breaks its chunk, one in the frontier's
    # group or past the live blocks breaks nothing
    ([1, 2, 3, 4, 5, 6, 9, 10] + list(range(20, 28)), 16 * BLOCK - 1,
     ["BROKEN", "WHOLE"]),
    ([1, 2, 3, 4, 5, 6, 9, 10] + list(range(20, 28)), 6 * BLOCK,
     ["TAIL", "TAIL"]),
    # a table popped backwards
    (list(range(16, 0, -1)), 12 * BLOCK, ["BROKEN", "BROKEN"]),
])
def test_a_chunks_form_follows_its_table(table, cached, want):
    """What the kernel is told of a chunk beside it: one copy a group in
    a straight line, the live groups and then the frontier's blocks, or
    a copy a block."""
    got = np.asarray(latent_attention._chunk_forms(
        jnp.asarray([table], jnp.int32), jnp.asarray([cached], jnp.int32),
        BLOCK, 8, 4))
    assert got.tolist() == [[getattr(latent_attention, w) for w in want]]


def test_the_rule_reads_the_tables_it_is_given(monkeypatch):
    """With no ``blocks_per_run`` the arena's shape decides (bfloat16
    rows of 128 lanes in blocks of 16: 4 KB a block, so the whole chunk
    of 8 pages a group), and a group may not lie across two chunks."""
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    q, arena, tables, lens = _case("seam", "bfloat16")
    got = np.asarray(latent_attention.latent_attention_decode(
        q, arena, tables, lens, scale=0.1, out_width=ROW,
        pages_per_chunk=PAGES))[..., :RANK]
    assert latent_attention.run_blocks(arena.shape, arena.dtype,
                                       MAX_BLOCKS) == PAGES
    want = _gather(q, arena, tables, lens, 0.1)
    active = LENS > 0
    assert np.abs(got[active] - want[active]).max() \
        <= 2.0 ** -6 * np.abs(want[active]).max()
    with pytest.raises(ValueError, match="whole groups"):
        latent_attention.latent_attention_decode(
            q, arena, tables, lens, scale=0.1, out_width=ROW,
            pages_per_chunk=PAGES, blocks_per_run=16)


@pytest.mark.parametrize("block,row,dtype,table,run", [
    (16, 640, "bfloat16", 256, 4),     # the reasoning cell: 80 KB a copy
    (64, 640, "bfloat16", 64, 1),      # a block of 64 carries that alone
    (16, 640, "float32", 256, 2),      # rows of twice the bytes
    (32, 640, "bfloat16", 128, 2),
    (16, 128, "bfloat16", 256, 16),    # 4 KB blocks: 16 of them
    (16, 128, "bfloat16", 4, 8),       # never more than a chunk's pages
])
def test_run_follows_the_block_bytes(block, row, dtype, table, run):
    """The smallest power of two of blocks that carries 64 KiB, a divisor
    of the chunk's pages: the arena's shape and dtype decide, as
    ``test_chunk_follows_the_row_bytes`` pins the paged chunk."""
    got = latent_attention.run_blocks((table * 4 + 1, block, row), dtype,
                                      table)
    assert got == run
    assert latent_attention._pages_per_chunk(block, table) % got == 0
