"""The fused attention, gated-delta-rule and grouped-experts kernels
compiled for a described (not attached) TPU v5e at real widths: what Mosaic refuses — a block shape off the
tiling, an unsupported relayout, too much VMEM — fails here, on the CPU,
before any chip time is spent. Nothing runs, so nothing here says
anything about results or speed.

One file, and the topology only inside a fixture: one process at a time
may load the TPU's library, and pytest-xdist workers all import every
test file.
"""

import re

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A program compiled for a described chip cannot be read back from
    the persistent cache without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


FUSED = ("flash_attention_fwd", "flash_attention_bwd")
SPLIT = ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv")
CASES = [   # the last column: the backward's kernels, by the shape's rule
    ((4, 1024, 16, 64), (4, 1024, 16, 64), jnp.bfloat16, True, FUSED),  # fit
    ((4, 1024, 16, 64), (4, 1024, 16, 64), jnp.float32, True, FUSED),
    ((1, 2048, 8, 128), (1, 2048, 8, 128), jnp.bfloat16, True, FUSED),
    ((2, 256, 20, 64), (2, 768, 20, 64), jnp.bfloat16, False, FUSED),
    # a whole dQ of 32k queries passes the VMEM budget: two kernels
    ((1, 32768, 2, 64), (1, 32768, 2, 64), jnp.bfloat16, True, SPLIT),
]


def test_flash_attention_compiles_for_v5e(monkeypatch, one_chip,
                                          no_compile_cache):
    """Forward and backward at the blocks the shapes choose: two
    Mosaic custom calls by their names, the backward one kernel, or
    three where the shape's rule (``backward_form``) sends dQ to a
    kernel of its own, and no (S, S) buffer in the program. One test over all the cases, so that one process (the one
    that holds the TPU's library) compiles them all, whatever the
    number of workers."""
    from flexflow_tpu.kernels import flash_attention as fa

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
    for q_shape, k_shape, dtype, causal, names in CASES:
        assert fa.supported(q_shape, k_shape, causal, dtype)
        q = jax.ShapeDtypeStruct(q_shape, dtype, sharding=one_chip)
        k = jax.ShapeDtypeStruct(k_shape, dtype, sharding=one_chip)

        def loss(q, k, v):
            return jnp.sum(fa.flash_attention(q, k, v, causal=causal)
                           .astype(jnp.float32))

        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, k, k).compile().as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == len(
            names), q_shape
        for name in names:
            assert name in text, (q_shape, name)
        (b, sq, h, _), skv = q_shape, k_shape[1]
        assert f"[{b},{h},{sq},{skv}]" not in text  # the scores of a head


SHARES = {   # picks, work_dim, width, count, gated, the widest wave
    "nemotron3-super-ep4": (22, 1024, 2688, 128, False, 4096),
    "axk1-ep16": (8, 7168, 2048, 12, True, 1536),
    "trinity-large-ep8": (4, 3072, 3072, 32, True, 2048)}


@pytest.mark.parametrize("cell, bucket", [
    (cell, bucket) for cell in ("nemotron3-super-ep4", "axk1-ep16")
    for bucket in (512, 768, 1024, "widest")] + [
    # a decode step's 32 slots, the one row behind a head's cut, a chunk
    ("trinity-large-ep8", 32), ("trinity-large-ep8", 1),
    ("trinity-large-ep8", 2048)])
def test_grouped_experts_compiles_for_v5e(monkeypatch, one_chip,
                                          no_compile_cache, cell, bucket):
    """A prefill's held experts at both routed cells' widths, the three
    buckets and the widest starting wave ``plan()`` takes (four prompts
    of 1,024 for the Nemotron share, whose sorted pairs nearly fill the
    scalar memory; two of 768 for the A.X-K1 share, whose rows and output
    leave the fast memory room for a weight block of 128 columns and no
    more), and the Trinity share's three calls (a decode step's 32 slots
    in ONE tile of 32 rows an expert named, 32 tiles and no more, an
    expert's matrices in two cuts; a head's one row padded to a sublane
    tile; a chunk of 2,048 in tiles of 128): one Mosaic custom call by
    its name, one sort (the
    pairs by held expert; no ``argsort`` pair, no stable sort), no
    ``conditional``, no ``while``, no gather but the table's own
    lookups, and no buffer a tile an expert or a row a pair: nothing
    beside the arguments but the packed rows, the float32 output and the
    table."""
    picks, work_dim, width, count, gated, widest = SHARES[cell]
    from flexflow_tpu.kernels import grouped_experts as kernel

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
    if bucket == "widest":
        bucket = widest
        assert kernel.plan(2 * bucket, picks, work_dim, width, count, gated,
                           jnp.bfloat16) is None
    assert kernel.supported(bucket, picks, work_dim, width, count, gated,
                            jnp.bfloat16)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    weights = {"w_up": sds((count, work_dim, width)),
               "w_down": sds((count, width, work_dim))}
    if gated:
        weights["w_gate"] = sds((count, work_dim, width))
    compiled = jax.jit(lambda v, ids, gates, w: kernel.grouped_experts(
        v, ids, gates, w, first=0, gated=gated)).lower(
        sds((bucket, work_dim)), sds((bucket, picks), jnp.int32),
        sds((bucket, picks), jnp.float32), weights).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "grouped_experts" in text
    assert len(re.findall(r" sort\(", text)) == 1
    for op in (" conditional(", " while("):
        assert op not in text, (cell, op)
    for ln in text.splitlines():      # the table's lookups: no row moves
        if " gather(" in ln:
            assert re.match(r"\s*(ROOT )?%?[\w.\-]+ = \w+\[\d+\]\{", ln), ln
    assert f"[{bucket * picks},{work_dim}]" not in text
    # the packed rows, the float32 output and its bfloat16 copy
    assert compiled.memory_analysis().temp_size_in_bytes <= (
        8 * max(bucket, 8) * work_dim + (1 << 20))
    if cell == "trinity-large-ep8":
        # a call of one tile's rows: the tile is the call's, a tile an
        # expert at the most, and the fast memory left takes wider cuts
        rows = max(bucket, 8)
        tile, tiles, cut = {8: (16, 32, 1536), 32: (32, 32, 1536),
                            2048: (128, 96, 1024)}[rows]
        assert kernel.tile_rows(rows) == tile
        assert kernel.grid_tiles(rows, picks, count) == tiles
        assert kernel.plan(bucket, picks, work_dim, width, count, gated,
                           jnp.bfloat16) == cut
        assert f"s32[{tiles}]" in text


def test_gated_delta_decode_compiles_for_v5e(monkeypatch, one_chip,
                                             no_compile_cache):
    """The state-update kernel at the published widths of the hybrid
    configuration (30 heads, keys of 96, values of 192: pairs of heads
    over three lane tiles), 32 slots over 33 rows: one Mosaic custom
    call, the arena aliased through it, nothing beside it on the device
    but what it is given."""
    from flexflow_tpu.kernels import gated_delta as gd

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
    n, rows, h, dk, dv = 32, 33, 30, 96, 192
    assert gd.supported(n, h, dk, dv, (rows, dk, h * dv), jnp.float32)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(arena, slot_rows, q, k, v, alpha, beta):
        return gd.gated_delta_decode(arena, slot_rows, q, k, v, alpha, beta)

    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        sds((rows, dk, h * dv)), sds((n,), jnp.int32), sds((n, h, dk)),
        sds((n, h, dk)), sds((n, h, dv)), sds((n, h)), sds((n, h))).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "gated_delta_decode" in text
    mem = compiled.memory_analysis()
    arena_bytes = rows * dk * h * dv * 4
    # the donated arena goes in and comes out as one buffer
    assert mem.alias_size_in_bytes >= arena_bytes
    assert mem.temp_size_in_bytes < arena_bytes // 8


def test_channel_decay_decode_compiles_for_v5e(monkeypatch, one_chip,
                                               no_compile_cache):
    """The same kernel with a decay a key channel at Ling-3.0-flash's KDA
    widths and its cell's slots (32 heads of 128 by 128, one head a lane
    tile; 256 slots over 257 rows of 2 MB): one Mosaic custom call, the
    arena aliased through it, the ``(N, d_k, H)`` decays one more operand
    beside the keys."""
    from flexflow_tpu.kernels import gated_delta as gd

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
    n, rows, h, dk, dv = 256, 257, 32, 128, 128
    assert gd.supported(n, h, dk, dv, (rows, dk, h * dv), jnp.float32)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(gd.gated_delta_decode, donate_argnums=(0,)).lower(
        sds((rows, dk, h * dv)), sds((n,), jnp.int32), sds((n, h, dk)),
        sds((n, h, dk)), sds((n, h, dv)), sds((n, h, dk)),
        sds((n, h))).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "gated_delta_decode" in text
    mem = compiled.memory_analysis()
    arena_bytes = rows * dk * h * dv * 4
    assert mem.alias_size_in_bytes >= arena_bytes
    assert mem.temp_size_in_bytes < arena_bytes // 8


@pytest.mark.parametrize("cell, rows, channels", [
    ("ling-3.0-flash-ep8.serve-longanswers", 257, 12288),
    ("olmo-hybrid-pp2.serve-documents", 33, 11520)])
def test_state_tails_step_compiles_for_v5e(monkeypatch, one_chip,
                                           no_compile_cache, cell, rows,
                                           channels):
    """The tails' kernel at both cells' arenas (257 rows of 3 x 12,288
    bfloat16, 33 of 3 x 11,520: 17 and 3 grid steps of one sublane tile of
    rows, the last hanging over the arena's edge): one Mosaic custom
    call, the arena aliased through it, nothing beside it on the device
    but what it is given and the float32 rows it returns."""
    from flexflow_tpu.kernels import gated_delta as gd

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
    bf = jnp.bfloat16
    assert gd.tails_supported((rows, 3 * channels), bf, channels)

    def sds(shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(gd.tails_step, donate_argnums=(0,)).lower(
        sds((rows, 3 * channels)), sds((rows,), jnp.bool_),
        sds((rows, channels)), sds((4, channels))).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "state_tails_step" in text
    mem = compiled.memory_analysis()
    arena_bytes = rows * 3 * channels * 2
    assert mem.alias_size_in_bytes >= arena_bytes
    assert mem.temp_size_in_bytes < arena_bytes // 8


@pytest.mark.parametrize("cell, n, rows, h, g", [
    ("granite-4.0-h-micro.serve-rag", 48, 49, 64, 1),
    ("nemotron3-super-ep4.serve-agents", 128, 129, 128, 8)])
def test_ssd_step_decode_compiles_for_v5e(monkeypatch, one_chip,
                                          no_compile_cache, cell, n, rows, h,
                                          g):
    """The Mamba-2 state-update kernel at the two cells' widths (heads of
    64 over a state of 128: rows of 2.1 and 4.2 MB, whole in the fast
    memory twice in and twice out): one Mosaic custom call, the arena
    aliased through it, nothing of its size beside it."""
    from flexflow_tpu.kernels import ssd_step

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
    p, s = 64, 128
    assert ssd_step.supported(n, h, p, s, g, (rows, s, h * p), jnp.float32)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(ssd_step.ssd_step_decode, donate_argnums=(0,)).lower(
        sds((rows, s, h * p)), sds((n,), jnp.int32), sds((n, h, p)),
        sds((n, h)), sds((n, g, s)), sds((n, g, s))).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "ssd_step_decode" in text
    mem = compiled.memory_analysis()
    arena_bytes = rows * s * h * p * 4
    assert mem.alias_size_in_bytes >= arena_bytes
    assert mem.temp_size_in_bytes < arena_bytes // 8


def test_decay_step_rows_compiles_for_v5e(one_chip, no_compile_cache):
    """The long-documents cell's decay step at its real widths
    (MiniCPM-SALA's 32 heads of 128 by 128 and ``serve-longdocs``' 16
    slots, read from the cell's files: 17 rows of 2 MB): each row TAKES
    its slot's q, k and v (``ops/rows.py`` ``named_by``) where a one-hot
    product over the slots multiplied them in, so no product of two
    (slots, rows) arrays is left, the states are stepped where they lie
    (no ``while``: a gather of rows of 2 MB lowers to a sequential loop
    over the slots; no scatter), and the donated arena goes in and comes
    out as one buffer."""
    import json
    import os

    from flexflow_tpu.ops.lightning_attention import decay_step_rows

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(root, "configs", "minicpm-sala-pp2.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "traffic", "serve-longdocs.json")) as f:
        n = json.load(f)["decode_slots"]
    h, d = config["lightning_nh"], config["lightning_head_dim"]
    assert (n, h, d) == (16, 32, 128)
    rows = n + 1

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(decay_step_rows, donate_argnums=(0,)).lower(
        sds((rows, h, d, d)), sds((n,), jnp.int32), sds((n, h, d)),
        sds((n, h, d)), sds((n, h, d)), sds((h,))).compile()
    text = compiled.as_text()
    assert " while(" not in text and "scatter" not in text
    # the one product left is a row's query against its own state
    assert f"f32[{n},{rows}]" not in text and f"f32[{rows},{n}]" not in text
    mem = compiled.memory_analysis()
    arena_bytes = rows * h * d * d * 4
    assert mem.alias_size_in_bytes >= arena_bytes
    assert mem.temp_size_in_bytes < arena_bytes // 8


@pytest.mark.parametrize("block, table, run", [(16, 256, 4), (64, 64, 1)])
def test_latent_attention_decode_compiles_for_v5e(monkeypatch, one_chip,
                                                  no_compile_cache, block,
                                                  table, run):
    """The latent decode kernel at the reasoning cell's shape (128 slots,
    64 heads over rows of 640 bfloat16 lanes, tables of 4,096 tokens): in
    blocks of 16 a group of 4 neighbours comes by one copy of a slice of
    the arena, in blocks of 64 every block by its own; one Mosaic custom
    call under the name the roofline's reader finds it by."""
    from flexflow_tpu.kernels import latent_attention as la

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
    n, heads, row, width = 128, 64, 640, 512
    arena = (n * table + 1, block, row)
    assert la.supported((n, heads, row), arena, jnp.bfloat16, table, width)
    assert la.run_blocks(arena, jnp.bfloat16, table) == run

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(lambda q, a, t, l: la.latent_attention_decode(
        q, a, t, l, scale=0.04, out_width=width)).lower(
        sds((n, heads, row)), sds(arena), sds((n, table), jnp.int32),
        sds((n,), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "latent_attention_decode" in text


def test_gated_delta_prefill_compiles_for_v5e(monkeypatch, one_chip,
                                              no_compile_cache):
    """One linear layer's prefill (``GatedDeltaNet.whole``: projections,
    convolution, the recurrence, norm and gate, from bfloat16 x and
    weights) at the hybrid configuration's widths and each of its cell's
    three buckets: one Mosaic custom call, named ``gated_delta_chunks``;
    no ``while`` (the scan's loops) anywhere in the program, no
    ``(n, 1, 30, 64, 64)`` buffer (the scan's decay, system and inverse a
    chunk), temporaries under 128 MB where the scan's program takes 228
    at the widest bucket. And twelve such layers trace the kernel once:
    the lowered text holds one function that all of them call."""
    from flexflow_tpu.core.layer import Layer
    from flexflow_tpu.core.parallel_tensor import ParallelTensorShape
    from flexflow_tpu.ffconst import DataType, OpType
    from flexflow_tpu.ops import gated_delta as op_mod

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
    e, h, dk, dv = 3840, 30, 96, 192
    layer = Layer(OpType.GATED_DELTA_NET, "gdn", attrs=dict(
        num_heads=h, key_dim=dk, value_dim=dv, conv_taps=4,
        allow_neg_eigval=True))
    op = op_mod.GatedDeltaNet(layer, [ParallelTensorShape.unpartitioned(
        (1, 1536, e), DataType.FLOAT)])

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    weights = {ws.name: sds(ws.shape) for ws in op.weight_specs()}
    lengths = sds((1,), jnp.int32)
    for bucket in (768, 1024, 1536):
        assert op_mod.delta_rule_path(bucket, h, dk, dv) == "kernel"
        compiled = jax.jit(op.whole).lower(
            weights, sds((1, bucket, e)), lengths).compile()
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1, bucket
        assert "gated_delta_chunks" in text
        assert "gated_delta_decode" not in text
        assert " while(" not in text, bucket
        assert ",1,30,64,64]" not in text, bucket
        assert compiled.memory_analysis().temp_size_in_bytes < 128 << 20

    def twelve(weights, x, lengths):
        for _ in range(12):
            x = op.whole(weights, x, lengths)[0]
        return x

    text = jax.jit(twelve).lower(weights, sds((1, 768, e)),
                                 lengths).as_text()
    assert text.count("tpu_custom_call") == 1
    assert text.count("func.func private @_gated_delta_chunks(") == 1
    assert text.count("call @_gated_delta_chunks(") == 12


def test_scalar_rule_traces_to_the_program_it_was(monkeypatch):
    """``GatedDeltaNet.whole`` at the documents cell's 1,536 bucket traces
    to the program it traced to on 62fe4b3, the commit before the
    whole-sequence kernel took a decay a key channel (sha256 of
    ``jax.make_jaxpr``'s text: the projections, the ``pallas_call`` with
    its kernel's body, grid, block shapes and index maps; source locations
    struck, as ``tests/test_mimo_lm.py`` keeps for the attention kernels):
    the per-channel form is a branch the scalar form never enters. A
    digest that moves with a change to the kernel's body is recorded
    anew, with the documents cell's numbers on the chip beside it."""
    import hashlib

    from flexflow_tpu.core.layer import Layer
    from flexflow_tpu.core.parallel_tensor import ParallelTensorShape
    from flexflow_tpu.ffconst import DataType, OpType
    from flexflow_tpu.ops import gated_delta as op_mod

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
    e, h, dk, dv = 3840, 30, 96, 192
    layer = Layer(OpType.GATED_DELTA_NET, "gdn", attrs=dict(
        num_heads=h, key_dim=dk, value_dim=dv, conv_taps=4,
        allow_neg_eigval=True))
    op = op_mod.GatedDeltaNet(layer, [ParallelTensorShape.unpartitioned(
        (1, 1536, e), DataType.FLOAT)])

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    traced = jax.make_jaxpr(op.whole)(
        {ws.name: sds(ws.shape) for ws in op.weight_specs()},
        sds((1, 1536, e)), sds((1,), jnp.int32))
    text = re.sub(r" at [^\s]+:\d+", "", str(traced))
    assert "name=gated_delta_chunks" in text
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "c567fcda8f46fef10e3b7148cba5a203d29526505fd41e69642d1491a14f0f97")


@pytest.fixture(scope="module")
def sparse_hybrid_programs(one_chip):
    """The decode step and both chunk programs of a sparse and a linear
    layer at MiniCPM-SALA's published widths (contexts to 33,792, chunks
    of 2,048, the whole vocabulary), compiled for the described chip:
    {name: (the compiled text, its memory analysis)}."""
    import json
    import os

    from jax.experimental.compilation_cache import compilation_cache

    from benchmark.families import minicpm_sala as family
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.ffconst import CompMode
    from flexflow_tpu.serving.generation import PagedDecoder
    from flexflow_tpu.serving.kv_cache import Addresses

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "minicpm-sala-pp2.json")) as f:
        config = json.load(f)
    config = dict(config, num_hidden_layers=2,
                  mixer_types=config["mixer_types"][:2])
    slots, max_length, chunk = 4, 33792, 2048
    ff = FFModel(FFConfig(batch_size=slots, compute_dtype="bfloat16",
                          ledger="off", search_cache="off",
                          computation_mode=CompMode.INFERENCE))
    family.build(ff, config, slots, max_length)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    dec = PagedDecoder(ff, max_length, decode_slots=slots, block_size=64,
                       prefill_chunk=chunk, kv_dtype="bfloat16",
                       calibrate=False)

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def ints(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, dec._params_sds())
    pool = jax.tree_util.tree_map(on_chip, dec.pool.kv)
    mb = dec.max_blocks_per_request
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        out = {}
        for head in (False, True):
            def program(*args, head=head):
                return dec._chunk_step(*args, head=head)

            compiled = jax.jit(program, donate_argnums=(2,)).lower(
                params, ints(1, chunk), pool, Addresses(ints(1, mb), ints(1)),
                ints(1), ints(1)).compile()
            out["chunk_head" if head else "chunk"] = (
                compiled.as_text(), compiled.memory_analysis())
        compiled = dec._decode.lower(
            params, ints(slots), pool,
            Addresses(ints(slots, mb), ints(slots)), ints(slots), {},
            ints(slots), ints(slots, dtype=jnp.bool_)).compile()
        out["decode"] = (compiled.as_text(), compiled.memory_analysis())
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return out


@pytest.mark.parametrize("name", ["chunk", "chunk_head", "decode"])
def test_sparse_hybrid_programs_hold_no_square_and_no_vocabulary_rows(
        sparse_hybrid_programs, name):
    """No buffer with two sequence axes (a chunk's 2,048 queries against
    the 33,792 positions, or against themselves for all heads), none of
    (tokens, vocabulary) beyond a row a request, and little beside the
    weights and the pool."""
    text, mem = sparse_hybrid_programs[name]
    shapes = set(re.findall(r"\[([0-9,]+)\]", text))
    for dims in shapes:
        dims = [int(d) for d in dims.split(",")]
        assert not (2048 in dims and 33792 in dims), dims
        assert dims.count(2048) < 2, dims
        if 73448 in dims:                  # the head's matrix, or one row
            rest = [d for d in dims if d != 73448]
            assert rest in ([], [4096], [1], [1, 1], [4], [4, 1]), dims
    assert mem.temp_size_in_bytes < 1 << 30


def test_sparse_hybrid_decode_loops_over_no_slots(sparse_hybrid_programs):
    """The decode step's states are updated where they lie and its blocks
    gathered by one gather: no ``while`` (a gather of rows of 2 MB lowers
    to a sequential loop over the slots), and the chunk programs' loops
    are at most the three they were written with (the linear layer's scan
    over sub-chunks, the selection a few queries at a time, the key
    spans)."""
    assert " while(" not in sparse_hybrid_programs["decode"][0]
    for name in ("chunk", "chunk_head"):
        assert 1 <= sparse_hybrid_programs[name][0].count(" while(") <= 3


def _buffers(text):
    """The instructions of an optimised HLO module that own a buffer:
    those of every computation that is no fusion's body and no reducer
    (what is inside a fusion lives in registers and VMEM)."""
    comps, cur = {}, None
    for ln in text.splitlines():
        if re.match(r"^(ENTRY )?%?[\w.\-]+ \(.*\) -> .* \{$", ln):
            cur = comps.setdefault(ln.split(" (", 1)[0].split("%")[-1], [])
        elif ln.startswith("}"):
            cur = None
        elif cur is not None and " = " in ln:
            cur.append(ln.strip())
    inner = set()
    for lines in comps.values():
        for ln in lines:
            inner.update(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", ln))
    return [ln for name, lines in comps.items() if name not in inner
            for ln in lines]


@pytest.fixture(scope="module")
def train_step_text(one_chip):
    """The optimised text of the fit cell's step program at two layers
    (hidden 1024, 4 x 1024 tokens, vocabulary 50,257, bfloat16, Adam, the
    fused attention kernels), compiled for the described chip."""
    from jax.experimental.compilation_cache import compilation_cache

    from flexflow_tpu import (AdamOptimizer, FFConfig, FFModel, LossType,
                              MetricsType, make_mesh)
    from flexflow_tpu.models.gpt import GPTConfig, build_gpt

    batch, seq, vocab = 4, 1024, 50257
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
            ff = FFModel(FFConfig(batch_size=batch, seed=0,
                                  search_cache="off", ledger="off",
                                  compute_dtype="bfloat16",
                                  only_data_parallel=True, search_budget=0))
            build_gpt(ff, batch, seq, GPTConfig(
                vocab_size=vocab, max_positions=seq, hidden_size=1024,
                num_heads=16, num_layers=2))
            ff.compile(optimizer=AdamOptimizer(alpha=2e-4),
                       loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                       metrics=[MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY],
                       mesh=make_mesh({"data": 1}, devices=jax.devices()[:1]))
            spec = ff.compiled.audit_exec[0]
            assert spec.name == "train_step"

            def on_chip(a):  # the optimizer's hyperparameters are plain floats
                return (jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=one_chip)
                        if hasattr(a, "shape") else a)

            seq_length, *args = spec.args
            args = jax.tree_util.tree_map(on_chip, args)
            args[-1] = jax.ShapeDtypeStruct(
                (batch, seq), jnp.int32, sharding=one_chip)  # a label a position
            return ff, spec.fn.lower(seq_length, *args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_train_step_holds_no_float32_vocabulary_array(train_step_text):
    """Sparse cross-entropy reads the head's logits where the head wrote
    them. The logits exist once, as bfloat16, and no float32 array
    of (tokens, vocabulary) is a buffer of the program — the log_softmax
    path held three (the float32 copy, the log-probabilities, and the
    scattered cotangent's reduction read them back)."""
    batch, seq, vocab = 4, 1024, 50257
    _, text = train_step_text
    assert "flash_attention_fwd" in text
    made = []  # (what an instruction's output is, the instruction)
    for ln in _buffers(text):
        # `%name = <type, or a tuple of them> opcode(operands), ...`
        m = re.search(r"[})] ([a-z][\w\-]*)\(", ln)
        if m.group(1) not in ("get-tuple-element", "bitcast", "parameter"):
            made.append((ln[:m.start() + 1], ln))

    def producers(shape):
        return [ln for out, ln in made if shape in out]

    assert not producers(f"f32[{batch},{seq},{vocab}]")
    assert not producers(f"f32[{batch * seq},{vocab}]")
    assert len(producers(f"bf16[{batch},{seq},{vocab}]")) == 1
    assert not producers(f"bf16[{batch * seq},{vocab}]")


# ---- the scopes survive the compiler -------------------------------------------

def _entry_op_names(text):
    """``{instruction: its op_name}`` over the computations that are not
    a fusion's own (what a device trace has an event for)."""
    out = {}
    for ln in _buffers(text):
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", ln)
        name = re.search(r'op_name="([^"]*)"', ln)
        if m:
            out[m.group(1)] = name.group(1) if name else ""
    return out


def test_train_step_fusions_keep_their_scopes(train_step_text):
    """What XLA fuses keeps an owner: the optimised step's fusions and
    kernels carry ``ff.`` scopes in their ``op_name``, every op of the
    graph that has weights owns some instruction forward and backward,
    and the loss and the update are there under their fixed scopes."""
    from flexflow_tpu.core.op import parse_scope, scope_group

    ff, text = train_step_text
    names = _entry_op_names(text)
    fusions = {k: v for k, v in names.items() if "fusion" in k}
    owned = {k: parse_scope(v) for k, v in fusions.items()}
    assert len(fusions) > 40
    assert sum(o is not None for o in owned.values()) > 0.9 * len(fusions)
    owners = {parse_scope(v) for v in names.values()} - {None}
    for op in ff.compiled.ops:
        if op.weight_specs():
            phases = {ph for k, n, _, ph in owners if n == op.name}
            assert phases == {"fwd", "bwd"}, (op, phases)
    kinds = {k for k, n, _, _ in owners if n == ""}
    assert {"loss", "optimizer"} <= kinds
    kernels = {k: parse_scope(v) for k, v in names.items()
               if "flash_attention" in k}
    assert kernels and all(
        o and scope_group(o[0]) == "attention" and o[2] == ("attend",)
        for o in kernels.values()), kernels


@pytest.mark.parametrize("compute_dtype, kv_dtype, tails_dtype", [
    ("bfloat16", "bfloat16", "bf16"),       # the documents cell's
    ("float32", "float32", "f32"),          # ``serving_kv_dtype``'s default
])
def test_hybrid_decode_step_loops_over_no_slots(monkeypatch, one_chip,
                                                no_compile_cache,
                                                compute_dtype, kv_dtype,
                                                tails_dtype):
    """One linear layer of the hybrid configuration at its published
    widths and its cell's 32 slots: the decode step holds no ``while``
    and no ``dynamic-update-slice`` (a scatter of the convolution tails,
    rows of 34,560 numbers, lowered to a sequential loop over the slots).
    The tails are stepped by ONE kernel over the arena's 33 rows
    (``state_tails_step``, PR 59; a fusion under ``write`` before it) that
    carries the state op's ``conv`` in its ``op_name``, in place (the
    arena's parameter is aliased to its output), and no gather in the
    program yields rows of 34,560; the state kernel carries its ``rule``:
    what a device trace reads them by."""
    import json
    import os

    from benchmark.families import olmo_hybrid as family
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.core.op import parse_scope
    from flexflow_tpu.ffconst import CompMode
    from flexflow_tpu.serving.generation import PagedDecoder
    from flexflow_tpu.serving.kv_cache import Addresses

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "olmo-hybrid-pp2.json")) as f:
        config = json.load(f)
    config = dict(config, num_hidden_layers=1,
                  layer_types=config["layer_types"][:1])
    slots, max_length = 32, 2048
    ff = FFModel(FFConfig(batch_size=slots, compute_dtype=compute_dtype,
                          ledger="off", search_cache="off",
                          computation_mode=CompMode.INFERENCE))
    family.build(ff, config, slots, max_length)
    ff.compile(optimizer=None, loss_type=None, metrics=[])
    dec = PagedDecoder(ff, max_length, decode_slots=slots, block_size=16,
                       kv_dtype=kv_dtype, calibrate=False)

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def ints(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (jax.tree_util.tree_map(on_chip, dec._params_sds()), ints(slots),
            jax.tree_util.tree_map(on_chip, dec.pool.kv),
            Addresses(ints(slots, dec.max_blocks_per_request), ints(slots)),
            ints(slots), {}, ints(slots), ints(slots, dtype=jnp.bool_))
    text = dec._decode.lower(*args).compile().as_text()
    assert " while(" not in text
    assert "dynamic-update-slice" not in text
    assert " scatter(" not in text
    (mixer,) = [op.name for op in dec._attn_ops]
    state, tails = dec.pool.kv[mixer]
    arena = f"{tails_dtype}[{tails.shape[0]},{tails.shape[1]}]"
    assert tails.shape == (slots + 1, 3 * 11520)
    names = _entry_op_names(text)
    # the entry's instructions that make a whole arena (the compiler may
    # besides move the float32 one through fast memory and back, an async
    # ``copy-done`` each way: the same bytes as a pass where it lies)
    made = {}
    for ln in _buffers(text):
        m = re.match(rf"\s*(?:ROOT )?%?([\w.\-]+) = {re.escape(arena)}\S* "
                     r"([a-z][\w\-]*)\(", ln)
        if m and m.group(2) not in ("parameter", "bitcast", "copy-done",
                                    "get-tuple-element"):
            made[m.group(1)] = m.group(2)
    assert made == {}, made
    # ... but the kernel's call, whose result is a tuple (the arena, the
    # convolved rows)
    (writer,) = [k for k in names if k.startswith("state_tails_step")]
    assert parse_scope(names[writer]) == (
        "GATED_DELTA_NET", mixer, ("conv",), "fwd")
    assert not re.search(rf"\[\d+,{tails.shape[1]}\]\S* gather\(", text)
    # no copy of either arena: both donated parameters alias their outputs
    leaves = jax.tree_util.tree_leaves(args)
    pooled = [i for i, a in enumerate(leaves)
              if a.shape in (state.shape, tails.shape)]
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    assert sorted(int(i) for i in re.findall(r"\((\d+), ", alias)) == pooled
    kernels = [parse_scope(v) for k, v in names.items()
               if k.startswith("gated_delta_decode")]
    assert kernels == [("GATED_DELTA_NET", mixer, ("rule",), "fwd")]


@pytest.mark.parametrize("name", ["chunk", "chunk_head", "decode"])
def test_sparse_hybrid_programs_name_their_pieces(sparse_hybrid_programs,
                                                  name):
    """At the published widths the compiled programs keep the pieces a
    metric sums: a chunk's ``sort`` lies under a sparse op's ``select``,
    its loops under ``attend`` (the walk over the key spans), ``select``
    (a few queries at a time) or a linear op's ``chunks``."""
    from flexflow_tpu.core.op import parse_scope, scope_group

    names = _entry_op_names(sparse_hybrid_programs[name][0])
    owners = {k: parse_scope(v) for k, v in names.items()}
    fusions = [o for k, o in owners.items() if "fusion" in k]
    assert sum(o is not None for o in fusions) > 0.9 * len(fusions)
    if name == "decode":
        return
    sorts = [o for k, o in owners.items() if k.startswith("sort")]
    assert sorts and all(
        o and o[0] == "BLOCK_SPARSE_ATTENTION" and o[2][-1] == "select"
        for o in sorts), sorts
    loops = {(scope_group(o[0]), o[2][-1]) for k, o in owners.items()
             if k.startswith("while") and o and o[2]}
    assert loops <= {("attention", "attend"), ("attention", "select"),
                     ("state", "chunks")}
    # the two layers end in the sparse one: a chunk that computes no head
    # has no use for what it attends, and the compiler drops the walk
    assert (("attention", "attend") in loops) == (name == "chunk_head")


@pytest.fixture(scope="module")
def nemotron_programs(one_chip):
    """The decode step and the widest prefill of one ``E``, one ``M`` and
    the ``*`` layer at Nemotron-3-Super's published widths and its cell's
    sizes (128 slots, contexts to 2,048, blocks of 16, 128 of 512 experts
    held, a quarter of the vocabulary), compiled for the described chip:
    {name: (the compiled text, its memory analysis)}, and the decoder."""
    import json
    import os

    from jax.experimental.compilation_cache import compilation_cache

    from benchmark.families import nemotron_h as family
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.core.machine import make_mesh
    from flexflow_tpu.ffconst import CompMode
    from flexflow_tpu.serving.generation import PagedDecoder
    from flexflow_tpu.serving.kv_cache import Addresses

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "nemotron3-super-ep4.json")) as f:
        config = json.load(f)
    config = dict(config, num_hidden_layers=3, hybrid_override_pattern="EM*")
    slots, max_length = 128, 2048
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
            ff = FFModel(FFConfig(batch_size=slots, compute_dtype="bfloat16",
                                  ledger="off", search_cache="off",
                                  computation_mode=CompMode.INFERENCE))
            family.build(ff, config, slots, max_length)
            # one device's model, as on the chip (over the eight virtual
            # CPU devices' mesh the experts keep their jnp form)
            ff.compile(optimizer=None, loss_type=None, metrics=[],
                       mesh=make_mesh(devices=jax.devices()[:1]))
            dec = PagedDecoder(ff, max_length, decode_slots=slots,
                               block_size=16, kv_dtype="bfloat16",
                               calibrate=False, prefill_buckets=[768, 1024])

            def on_chip(a):
                return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=one_chip)

            def ints(*shape, dtype=jnp.int32):
                return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

            params = jax.tree_util.tree_map(on_chip, dec._params_sds())
            pool = jax.tree_util.tree_map(on_chip, dec.pool.kv)
            acc = jax.tree_util.tree_map(on_chip, dec._expert_acc)
            mb = dec.max_blocks_per_request
            out = {}
            compiled = dec._decode.lower(
                params, ints(slots), pool,
                Addresses(ints(slots, mb), ints(slots)), ints(slots), acc,
                ints(slots), ints(slots, dtype=jnp.bool_)).compile()
            out["decode"] = (compiled.as_text(), compiled.memory_analysis())
            for name, bucket in (("prefill", 1024), ("prefill_768", 768)):
                compiled = jax.jit(
                    dec._prefill_step, donate_argnums=(2,)).lower(
                    params, ints(1, bucket), pool,
                    Addresses(ints(1, mb), ints(1)), ints(1)).compile()
                out[name] = (compiled.as_text(), compiled.memory_analysis())
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return out, dec


def test_nemotron_decode_step_loops_over_no_slots(nemotron_programs):
    """The decode step of a state-space, a latent-expert and a
    grouped-head layer holds no ``while``, no ``dynamic-update-slice``
    and no scatter but the new token's keys and values (the states are
    stepped where they lie, the tails spread over the arena's 129 rows);
    its Mosaic calls are the
    paged kernel over 2 key-value heads of 128 (``attention_path``
    ``kernel``), the ``M`` op's one ``ssd_step_decode`` call and, in the
    one arm of the ``E`` op's ``conditional``, ``grouped_experts``; the
    state arena (129 x 128 x 8,192 float32, 541 MB) is made by that
    call alone, under the op's ``rule``, and both of the op's arenas
    alias their outputs: nothing beside the weights and the pool but
    64 MB."""
    from flexflow_tpu.core.op import parse_scope

    programs, dec = nemotron_programs
    text, mem = programs["decode"]
    assert dec.attention_path == {"decode": "kernel", "chunk": None,
                                  "decode_chunk_tokens": 256}
    assert " while(" not in text
    assert "dynamic-update-slice" not in text
    # the only scatter is the ``*`` layer's: a token's keys and values
    # into its block, as in every pair entry's step
    for ln in text.splitlines():
        if " scatter(" in ln:
            assert "ff.MULTIHEAD_ATTENTION.block2_mixer/write" in ln, ln
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "paged_attention_decode" in text
    names = _entry_op_names(text)
    made = {}
    for ln in _buffers(text):       # a result, or one of a tuple of them
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) "
                     r"([a-z][\w\-]*)\(", ln)
        if (m and "f32[129,128,8192]" in m.group(2)
                and m.group(3) not in ("parameter", "get-tuple-element",
                                       "tuple")):
            assert "ssd_step_decode" in ln, ln
            made[m.group(1)] = m.group(3)
    assert list(made.values()) == ["custom-call"], made
    (writer,) = made
    assert parse_scope(names[writer]) == (
        "MAMBA2", "block1_mixer", ("rule",), "fwd")
    assert mem.temp_size_in_bytes < 64 << 20
    assert mem.alias_size_in_bytes >= dec.pool.memory_bytes()


def test_nemotron_programs_name_their_pieces(nemotron_programs):
    """What the owner table reads: the expert op's ``route``, ``latent``
    and ``experts`` and the state-space op's ``project``, ``conv``,
    ``rule`` and ``write`` are in both programs' ``op_name`` paths; the
    decode step's held experts are counted (``expert_form``: 128 slots
    of 22 picks over 512 can name every one of the 128 held, so the step
    counts those its live rows do name): ONE ``conditional`` an expert
    layer, whose one arm multiplies every slot through every held expert
    (a (128, 128, 2688) product) and whose other is the kernel, both
    under the op's ``experts``, and the op's counters carry the two words
    behind the experts' rows; the prefill's held experts are ONE Mosaic
    call an expert layer, under the op's ``experts`` (the owner table and
    ``prefill_experts_device_ms.agents`` read it there), with no
    ``conditional`` in the program and no ``while`` of the experts', no
    dense form beside it and no float32 buffer a tile an expert."""
    from flexflow_tpu.core.op import parse_scope

    programs, dec = nemotron_programs
    for name in ("decode", "prefill"):
        text = programs[name][0]
        owners = {parse_scope(m) for m in re.findall(
            r'op_name="([^"]+)"', text)} - {None}
        subs = {(kind, sub) for kind, _, subs_, _ in owners for sub in subs_}
        assert {("ROUTED_EXPERTS", "route"), ("ROUTED_EXPERTS", "latent"),
                ("ROUTED_EXPERTS", "experts"), ("MAMBA2", "project"),
                ("MAMBA2", "conv"), ("MAMBA2", "rule"),
                ("MAMBA2", "write")} <= subs, (name, subs)
    decode, prefill = programs["decode"][0], programs["prefill"][0]
    assert "[128,128,2688]" in decode
    assert decode.count(" conditional(") == 1
    (call,) = [ln for ln in decode.splitlines() if "grouped_experts" in ln
               and 'custom_call_target="tpu_custom_call"' in ln]
    assert "/experts/" in call and "f32[128,1024]" in call
    (op,) = dec._expert_ops
    assert 1 <= op.kernel_limit() < 128
    assert dec._expert_acc[op.name].shape == (2, 4 + 128 + 2)
    assert " conditional(" not in prefill
    # the only loop left is the state-space op's walk over its chunks
    for ln in prefill.splitlines():
        if " while(" in ln:
            assert "ff.MAMBA2." in ln and "/rule/" in ln, ln
    calls = [ln for ln in prefill.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln
             and "grouped_experts" in ln]
    assert len(calls) == 1          # the fixture's pattern: one E layer
    (scope,) = re.findall(r'op_name="([^"]+)"', calls[0])
    assert parse_scope(scope)[:3] == ("ROUTED_EXPERTS", "block0_mixer",
                                      ("experts",))
    for gone in ("[128,256,2688]", "[136,256,2688]", "f32[128,1024,2688]",
                 "f32[1024,128,2688]"):
        assert gone not in prefill, gone
    assert programs["prefill"][1].temp_size_in_bytes < 3 << 30


@pytest.fixture(scope="module")
def trinity_programs(one_chip):
    """The decode step and both chunk programs of one dense windowed, one
    full and one windowed expert layer at Trinity-Large's published
    widths and its cell's sizes (32 slots, contexts to 17,408, blocks of
    64, chunks of 2,048, 32 of 256 experts held, an eighth of the
    vocabulary), compiled for the described chip: {name: (the compiled
    text, its memory analysis)}, and the decoder."""
    import json
    import os

    from jax.experimental.compilation_cache import compilation_cache

    from benchmark.families import trinity as family
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.core.machine import make_mesh
    from flexflow_tpu.ffconst import CompMode
    from flexflow_tpu.serving.generation import PagedDecoder
    from flexflow_tpu.serving.kv_cache import Addresses

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "trinity-large-ep8.json")) as f:
        config = json.load(f)
    config = dict(config, num_hidden_layers=3, layer_types=[
        "sliding_attention", "full_attention", "sliding_attention"])
    slots, max_length, chunk = 32, 17408, 2048
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
            ff = FFModel(FFConfig(batch_size=slots, compute_dtype="bfloat16",
                                  ledger="off", search_cache="off",
                                  computation_mode=CompMode.INFERENCE))
            family.build(ff, config, slots, max_length)
            ff.compile(optimizer=None, loss_type=None, metrics=[],
                       mesh=make_mesh(devices=jax.devices()[:1]))
            dec = PagedDecoder(ff, max_length, decode_slots=slots,
                               block_size=64, kv_dtype="bfloat16",
                               calibrate=False, prefill_chunk=chunk)

            def on_chip(a):
                return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=one_chip)

            def ints(*shape, dtype=jnp.int32):
                return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

            params = jax.tree_util.tree_map(on_chip, dec._params_sds())
            pool = jax.tree_util.tree_map(on_chip, dec.pool.kv)
            acc = jax.tree_util.tree_map(on_chip, dec._expert_acc)
            mb = dec.max_blocks_per_request
            out = {}
            compiled = dec._decode.lower(
                params, ints(slots), pool,
                Addresses(ints(slots, mb), ints(slots)), ints(slots), acc,
                ints(slots), ints(slots, dtype=jnp.bool_)).compile()
            out["decode"] = (compiled.as_text(), compiled.memory_analysis())
            for name, head in (("chunk", False), ("chunk_head", True)):
                compiled = jax.jit(
                    lambda *a, head=head: dec._chunk_step(*a, head=head),
                    donate_argnums=(2,)).lower(
                    params, ints(1, chunk), pool,
                    Addresses(ints(1, mb), ints(1)), ints(1),
                    ints(1)).compile()
                out[name] = (compiled.as_text(), compiled.memory_analysis())
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return out, dec


def test_trinity_decode_step_holds_no_while(trinity_programs):
    """The decode step of windowed and full layers holds no ``while`` and
    no ``conditional``; its Mosaic calls are the paged kernel, one an
    attention layer, the windowed layers' over their rings (48 query
    heads on 8 key-value heads of 128: ``attention_path`` ``kernel``),
    and the grouped-experts kernel, one an expert layer: 32 slots of 4
    picks over 256 name two in five of the 32 held, so the step reads
    the named experts' matrices and not all (``expert_form``), and no
    buffer holds every slot's row through every held expert; the
    only scatters are the new token's keys and values; the pool (the
    full layer's blocks and the rings) aliases its outputs; the op's
    counters carry the word the kernel's rows are counted in."""
    programs, dec = trinity_programs
    text, mem = programs["decode"]
    assert dec.attention_path == {"decode": "kernel", "chunk": "kernel",
                                  "decode_chunk_tokens": 256}
    assert " while(" not in text and " conditional(" not in text
    for ln in text.splitlines():
        if " scatter(" in ln:
            assert re.search(r"ff\.MULTIHEAD_ATTENTION\.block\d_attn/write",
                             ln), ln
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 3 + 2
    assert sum("paged_attention_decode" in c for c in calls) == 3
    assert sum("grouped_experts" in c for c in calls) == 2
    assert [a.shape for a in dec._expert_acc.values()] == [(2, 4 + 32 + 1)] * 2
    # a ring is 64 blocks a row and 33 rows; the full layer's arena every
    # slot's worst case
    shapes = {name: a[0].shape for name, a in dec.pool.kv.items()}
    assert shapes["block0_attn"] == shapes["block2_attn"] \
        == (33 * 64, 64, 1024)
    assert shapes["block1_attn"] == (32 * 272 + 1, 64, 1024)
    assert mem.alias_size_in_bytes >= dec.pool.memory_bytes()
    # no slot goes through every held expert: the dense form's 32 rows
    # an expert are gone, and so is a tile of 128 rows
    assert "[32,32,3072]" not in text and "[128,3072]" not in text


@pytest.mark.parametrize("name", ["chunk", "chunk_head"])
def test_trinity_chunk_programs_hold_no_square(trinity_programs, name):
    """A chunk of 2,048 queries over up to 17,408 keys: no buffer of two
    sequence axes at all (the attention of each layer that attends is
    ONE Mosaic call, ``chunk_attention``, its scores in VMEM; the span
    walk it replaces wrote 48 x 2,048 x 512 float32 a span, 201 MB, and
    its ``while`` is gone), the experts' products ONE Mosaic call an expert
    layer that runs (the last layer's runs in the head's chunk only, for
    the head's row alone, padded to a sublane tile: one call of its own,
    and no row goes through every held expert), and the pieces named:
    ``window`` inside ``attend`` in the windowed layers, ``gate``,
    ``route``, ``experts``."""
    from flexflow_tpu.core.op import parse_scope

    text, mem = trinity_programs[0][name]
    for ln in _buffers(text):
        for shape in re.findall(r"\[([\d,]+)\]", ln.split(" = ")[1]
                                .split("(")[0] if " = " in ln else ""):
            dims = [int(d) for d in shape.split(",")]
            wide = [d for d in dims if d >= 2048]
            assert len(wide) < 2 or 3072 in dims or 6144 in dims \
                or 12288 in dims or 25024 in dims, ln
    owners = {parse_scope(m) for m in re.findall(r'op_name="([^"]+)"', text)
              } - {None}
    subs = {(kind, s) for kind, _, ss, _ in owners for s in ss}
    assert {("MULTIHEAD_ATTENTION", "window"),
            ("MULTIHEAD_ATTENTION", "attend"),
            ("MULTIHEAD_ATTENTION", "gate"),
            ("MULTIHEAD_ATTENTION", "project"),
            ("MULTIHEAD_ATTENTION", "write"),
            ("ROUTED_EXPERTS", "route"),
            ("ROUTED_EXPERTS", "experts")} <= subs, subs
    mosaic = [ln for ln in text.splitlines()
              if 'custom_call_target="tpu_custom_call"' in ln]
    attends = [ln for ln in mosaic if "chunk_attention" in ln]
    # (a chunk that is not its prompt's last ends behind the last
    # attention op's write: nothing reads what that op would attend)
    assert len(attends) == (3 if name == "chunk_head" else 2)
    assert " while(" not in text
    assert {parse_scope(re.search(r'op_name="([^"]+)"', ln).group(1))[2]
            for ln in attends} == {("attend",), ("attend", "window")}
    calls = [ln for ln in mosaic if "grouped_experts" in ln]
    assert len(calls) == (2 if name == "chunk_head" else 1)
    assert sum("f32[2048,3072]" in c for c in calls) == 1
    assert sum("f32[8,3072]" in c for c in calls) == len(calls) - 1
    assert "[32,1,3072]" not in text
    assert mem.temp_size_in_bytes < 2 << 30
    # a request's ring is gathered in the arena's own layout: nothing
    # copies a whole arena (a ring arena is 33 x 4,096 x 1,024 numbers)
    for ln in text.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* copy\(", ln)
        if m:
            size = 1
            for d in m.group(1).split(","):
                size *= int(d)
            assert size < 33 * 4096 * 1024, ln


# ---- Granite 4.0-H: states through chunks, a tied head, scaled residuals ------

def _granite_programs(one_chip, **overrides):
    """The decode step and both chunk programs of two Mamba-2 blocks, an
    attention block and a Mamba-2 block (the published layers 3 to 6) at
    Granite 4.0-H Micro's published widths and its cell's sizes (48
    slots, contexts to 9,216, blocks of 64, chunks of 2,048, the whole
    vocabulary), compiled for the described chip: {name: (the compiled
    text, its memory analysis)}, and the decoder."""
    import json
    import os

    from jax.experimental.compilation_cache import compilation_cache

    from benchmark.families import granite_hybrid as family
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.core.machine import make_mesh
    from flexflow_tpu.ffconst import CompMode
    from flexflow_tpu.serving.generation import PagedDecoder
    from flexflow_tpu.serving.kv_cache import Addresses

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        config = json.load(f)
    config = dict(config, num_hidden_layers=4,
                  layer_types=config["layer_types"][3:7], **overrides)
    assert config["layer_types"] == ["mamba", "mamba", "attention", "mamba"]
    slots, max_length, chunk = 48, 9216, 2048
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
            ff = FFModel(FFConfig(batch_size=slots, compute_dtype="bfloat16",
                                  ledger="off", search_cache="off",
                                  computation_mode=CompMode.INFERENCE))
            family.build(ff, config, slots, max_length)
            ff.compile(optimizer=None, loss_type=None, metrics=[],
                       mesh=make_mesh(devices=jax.devices()[:1]))
            dec = PagedDecoder(ff, max_length, decode_slots=slots,
                               block_size=64, kv_dtype="bfloat16",
                               calibrate=False, prefill_chunk=chunk)

            def on_chip(a):
                return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=one_chip)

            def ints(*shape, dtype=jnp.int32):
                return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

            params = jax.tree_util.tree_map(on_chip, dec._params_sds())
            pool = jax.tree_util.tree_map(on_chip, dec.pool.kv)
            acc = jax.tree_util.tree_map(on_chip, dec._expert_acc)
            mb = dec.max_blocks_per_request
            out = {}
            compiled = dec._decode.lower(
                params, ints(slots), pool,
                Addresses(ints(slots, mb), ints(slots)), ints(slots), acc,
                ints(slots), ints(slots, dtype=jnp.bool_)).compile()
            out["decode"] = (compiled.as_text(), compiled.memory_analysis())
            for name, head in (("chunk", False), ("chunk_head", True)):
                if overrides:
                    continue
                compiled = jax.jit(
                    lambda *a, head=head: dec._chunk_step(*a, head=head),
                    donate_argnums=(2,)).lower(
                    params, ints(1, chunk), pool,
                    Addresses(ints(1, mb), ints(1)), ints(1),
                    ints(1)).compile()
                out[name] = (compiled.as_text(), compiled.memory_analysis())
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return out, dec


@pytest.fixture(scope="module")
def granite_programs(one_chip):
    return _granite_programs(one_chip)


def test_granite_decode_step_holds_no_while_and_moves_no_state(
        granite_programs):
    """The decode step of Mamba-2 and attention blocks holds no ``while``,
    no ``conditional`` and no ``dynamic-update-slice``; no gather or
    scatter touches a state (three arenas of 49 x 128 x 4,096 float32,
    103 MB each), each stepped by ONE ``ssd_step_decode`` kernel call
    under its op's ``rule`` with the arena aliased in and out, and no
    array of an arena's shape is made besides (the fusion this replaced
    was staged by the compiler: each arena copied into fast memory in
    four slices and back out by a copy of its own, which carry no
    scope); the slots' order is sorted ONCE for the three layers; the
    only scatter is the new token's keys and values; the attention layer
    reads its blocks in place by the paged kernel, 32 query heads on 8
    key-value heads of 64, two of them a lane tile (``attention_path``
    ``kernel``, and no copy of every slot's table: the gather it
    replaces made 48 x 144 x 64 x 512 bfloat16 twice, 0.9 GB, and was 36
    of the step's 60 ms on the chip); the tied head is ONE product that
    reads the embedding's table where it lies: no buffer of the table's
    size is made, transposed or not."""
    from flexflow_tpu.core.op import parse_scope

    programs, dec = granite_programs
    text, mem = programs["decode"]
    assert dec.attention_path == {"decode": "kernel", "chunk": "scan",
                                  "decode_chunk_tokens": 512}
    assert " while(" not in text and " conditional(" not in text
    assert "dynamic-update-slice" not in text
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    paged = [ln for ln in calls if "paged_attention_decode" in ln]
    assert len(paged) == 1 and len(calls) == 4
    assert "ff.MULTIHEAD_ATTENTION.block2_mixer/attend" in paged[0]
    state = "f32[49,128,4096]"
    for ln in _buffers(text):
        head = ln.split(" = ", 1)[1]
        if " gather(" in ln or " scatter(" in ln:
            assert "[48,128,4096]" not in head.split("(")[0] \
                and state not in head, ln
        if " scatter(" in ln:
            assert "ff.MULTIHEAD_ATTENTION.block2_mixer/write" in ln, ln
    assert sum(" sort(" in ln for ln in _buffers(text)) == 1
    names = _entry_op_names(text)
    made = {}
    for ln in _buffers(text):
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) "
                     r"([a-z][\w\-]*)\(", ln)
        if (m and state in m.group(2)
                and m.group(3) not in ("parameter", "get-tuple-element",
                                       "tuple")):
            assert "ssd_step_decode" in ln, ln
            made[m.group(1)] = m.group(3)
    assert sorted(made.values()) == ["custom-call"] * 3, made
    assert {parse_scope(names[w])[:3] for w in made} == {
        ("MAMBA2", f"block{i}_mixer", ("rule",)) for i in (0, 1, 3)}
    for ln in calls:
        if "ssd_step_decode" in ln:     # the arena in is the arena out
            operands = re.search(r"custom-call\((.*?)\), custom_call_target",
                                 ln).group(1).split(", ")
            aliased = int(re.search(
                r"output_to_operand_aliasing=\{\{0\}: \((\d+), \{\}\)\}",
                ln).group(1))
            assert re.search(r"%pool__block\d_mixer___0_", operands[aliased])
    assert mem.alias_size_in_bytes >= dec.pool.memory_bytes()
    # the table: the parameter, and nothing else of its size at all
    for ln in _buffers(text):
        if "[100352,2048]" in ln or "[2048,100352]" in ln:
            assert " parameter(" in ln, ln
    heads = [ln for ln in _buffers(text) if "ff.LINEAR.lm_head" in ln
             and "dot_general" in ln]
    assert len(heads) == 1 and "embed" in heads[0].split("fusion(")[1]
    assert "lm_head" not in dec._params_sds()
    # no slot's table is copied, and little else is held
    assert "[48,144,64,512]" not in text and "[48,9216," not in text
    assert mem.temp_size_in_bytes < 128 << 20


def test_granite_multipliers_cost_no_pass_of_their_own(one_chip,
                                                       granite_programs):
    """The embedding's multiplier, the residuals' 0.22 and the logits'
    divisor are ``SCALAR_MULTIPLY`` ops of the graph: the compiler fuses
    each into a neighbour, so the decode step with them holds no more
    device operations (what a trace has an event for) than the same
    model with all three at 1, where the builder adds no such op."""
    with_them = granite_programs[0]["decode"][0]
    without, dec = _granite_programs(one_chip, embedding_multiplier=1,
                                     residual_multiplier=1.0,
                                     logits_scaling=1)
    assert not [op for op in dec._cm.ops if "scale" in op.name]
    assert [op.name for op in granite_programs[1]._cm.ops
            if op.name.endswith("_scale")] == [
        "embed_scale", *(f"block{i}_{p}_scale" for i in range(4)
                         for p in ("mixer", "mlp")), "logits_scale"]

    def kernels(text):
        return sum(bool(re.search(r" (fusion|convolution|custom-call|"
                                  r"gather|scatter|copy|dot)\(", ln))
                   for ln in _buffers(text))

    assert kernels(with_them) <= kernels(without["decode"][0])


@pytest.mark.parametrize("name", ["chunk", "chunk_head"])
def test_granite_chunk_programs_carry_the_states_under_their_scopes(
        granite_programs, name):
    """A chunk of 2,048 tokens behind the states: the Mamba ops' pieces
    are named (``project``, ``conv``, ``rule``, ``write``: the owner table
    tells a chunk's scan from its projections), the attention op's
    ``attend`` and ``write``; the only loops are the scan's walk over its
    blocks of 256 and the attention's over its key spans; no buffer holds
    the chunk's queries against the whole context, and none a row of the
    vocabulary for every token (the head is computed for the last row
    alone: ``chunk`` holds no product with the table at all)."""
    from flexflow_tpu.core.op import parse_scope

    text, mem = granite_programs[0][name]
    owners = {parse_scope(m) for m in re.findall(r'op_name="([^"]+)"', text)
              } - {None}
    subs = {(kind, s) for kind, _, ss, _ in owners for s in ss}
    assert {("MAMBA2", "project"), ("MAMBA2", "conv"), ("MAMBA2", "rule"),
            ("MAMBA2", "write"), ("MULTIHEAD_ATTENTION", "project"),
            ("MULTIHEAD_ATTENTION", "write"),
            ("MULTIHEAD_ATTENTION", "attend")} <= subs, subs
    for ln in text.splitlines():
        if " while(" in ln:
            assert ("ff.MAMBA2." in ln and "/rule/" in ln) or (
                "ff.MULTIHEAD_ATTENTION." in ln and "/attend/" in ln), ln
    for ln in _buffers(text):
        for shape in re.findall(r"\[([\d,]+)\]", ln.split(" = ")[1]
                                .split("(")[0] if " = " in ln else ""):
            dims = [int(d) for d in shape.split(",")]
            # (the hidden size is the chunk's length: a square of 2,048
            # says nothing here; the context's length does)
            assert not (2048 in dims and 9216 in dims), ln
            assert not (2048 in dims and 100352 in dims) \
                or " parameter(" in ln, ln
    has_head = any("ff.LINEAR.lm_head" in ln for ln in _buffers(text))
    assert has_head == (name == "chunk_head")
    assert mem.alias_size_in_bytes >= granite_programs[1].pool.memory_bytes()
    assert mem.temp_size_in_bytes < 1 << 30


# ---- ZAYA1: a pair a token and a tail a request in one layer, an MLP router ----

@pytest.fixture(scope="module")
def zaya_programs(one_chip):
    """The decode step and both chunk programs of three layers at
    ZAYA1-8B's published widths and its cell's sizes (48 slots, contexts
    to 4,608, blocks of 64, chunks of 2,048, all 16 experts, the whole
    vocabulary), compiled for the described chip: {name: (the compiled
    text, its memory analysis)}, and the decoder."""
    import json
    import os

    from jax.experimental.compilation_cache import compilation_cache

    from benchmark.families import zaya as family
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.core.machine import make_mesh
    from flexflow_tpu.ffconst import CompMode
    from flexflow_tpu.serving.generation import PagedDecoder
    from flexflow_tpu.serving.kv_cache import Addresses

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "zaya1-8b-pp2.json")) as f:
        config = json.load(f)
    config = dict(config, num_hidden_layers=3, layer_types=["hybrid"] * 3)
    slots, max_length, chunk = 48, 4608, 2048
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
            ff = FFModel(FFConfig(batch_size=slots, compute_dtype="bfloat16",
                                  ledger="off", search_cache="off",
                                  computation_mode=CompMode.INFERENCE))
            family.build(ff, config, slots, max_length)
            ff.compile(optimizer=None, loss_type=None, metrics=[],
                       mesh=make_mesh(devices=jax.devices()[:1]))
            dec = PagedDecoder(ff, max_length, decode_slots=slots,
                               block_size=64, kv_dtype="bfloat16",
                               calibrate=False, prefill_chunk=chunk)

            def on_chip(a):
                return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=one_chip)

            def ints(*shape, dtype=jnp.int32):
                return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

            params = jax.tree_util.tree_map(on_chip, dec._params_sds())
            pool = jax.tree_util.tree_map(on_chip, dec.pool.kv)
            acc = jax.tree_util.tree_map(on_chip, dec._expert_acc)
            mb = dec.max_blocks_per_request
            out = {}
            compiled = dec._decode.lower(
                params, ints(slots), pool,
                Addresses(ints(slots, mb), ints(slots)), ints(slots), acc,
                ints(slots), ints(slots, dtype=jnp.bool_)).compile()
            out["decode"] = (compiled.as_text(), compiled.memory_analysis())
            for name, head in (("chunk", False), ("chunk_head", True)):
                compiled = jax.jit(
                    lambda *a, head=head: dec._chunk_step(*a, head=head),
                    donate_argnums=(2,)).lower(
                    params, ints(1, chunk), pool,
                    Addresses(ints(1, mb), ints(1)), ints(1),
                    ints(1)).compile()
                out[name] = (compiled.as_text(), compiled.memory_analysis())
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return out, dec


def test_zaya_decode_step_reads_the_pair_by_the_kernel(zaya_programs):
    """The decode step of three CCA and top-1 expert layers holds no
    ``while``; its Mosaic calls are the paged
    kernel, one a layer (8 query heads on 2 key-value heads of 128:
    ``attention_path`` ``kernel``), and the grouped-experts kernel, one
    an expert layer, in the one arm of that layer's ``conditional``: 48
    slots of one pick over 16 CAN name nearly every expert, so the step
    counts the experts its live rows DO name (``expert_form``
    ``counted``) and reads those alone up to 14 of the 16, all of them
    in the dense form beyond; the matrices are copied for neither arm;
    the op's counters carry the kernel's rows and the steps that took
    it; the only scatters are the new token's keys and
    values (the tails and half values go back through ``spread_rows``);
    the pool, pairs and rows, aliases its outputs."""
    programs, dec = zaya_programs
    text, mem = programs["decode"]
    assert dec.attention_path == {"decode": "kernel", "chunk": "kernel",
                                  "decode_chunk_tokens": 1024}
    assert " while(" not in text
    conds = [ln for ln in text.splitlines() if " conditional(" in ln]
    assert [re.search(r"ff\.ROUTED_EXPERTS\.(block\d)_experts/experts/",
                      ln).group(1) for ln in conds] == [
        "block0", "block1", "block2"]
    for ln in text.splitlines():
        if " scatter(" in ln:
            assert re.search(
                r"ff\.COMPRESSED_CONV_ATTENTION\.block\d_attn/write", ln), ln
        assert not re.search(r"= bf16\[16,2048,2048\]\S* copy\(", ln), ln
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 3 + 3
    assert sum("paged_attention_decode" in c for c in calls) == 3
    assert sum("grouped_experts" in c and "/experts/" in c
               for c in calls) == 3
    assert {op.kernel_limit() for op in dec._expert_ops} == {14}
    assert [a.shape for a in dec._expert_acc.values()] == [
        (2, 4 + 16 + 2)] * 3
    shapes = {name: [a.shape for a in entry]
              for name, entry in dec.pool.kv.items()}
    assert shapes["block0_attn"] == shapes["block2_attn"] == [
        (48 * 72 + 1, 64, 256)] * 2 + [(49, 2 * 1280), (49, 128)]
    assert dec.pool.memory_bytes() == 3 * (
        (48 * 72 + 1) * 64 * 1024 + 49 * 5376)
    assert mem.alias_size_in_bytes >= dec.pool.memory_bytes()


@pytest.mark.parametrize("name", ["chunk", "chunk_head"])
def test_zaya_chunk_programs_name_their_pieces(zaya_programs, name):
    """A chunk of 2,048 queries behind up to 4,608 keys: each layer that
    attends is ONE Mosaic call, ``chunk_attention``, no ``while``, and
    the pieces are named: ``project``, ``mix``, ``write``, ``attend``,
    ``out`` in the attention op, ``route`` and ``experts`` in the expert
    op; the head is computed in the head's chunk alone."""
    from flexflow_tpu.core.op import parse_scope

    text, mem = zaya_programs[0][name]
    owners = {parse_scope(m) for m in re.findall(r'op_name="([^"]+)"', text)
              } - {None}
    subs = {(kind, s) for kind, _, ss, _ in owners for s in ss}
    cca = "COMPRESSED_CONV_ATTENTION"
    assert {(cca, "project"), (cca, "mix"), (cca, "write"), (cca, "attend"),
            (cca, "out"), ("ROUTED_EXPERTS", "route"),
            ("ROUTED_EXPERTS", "experts")} <= subs, subs
    mosaic = [ln for ln in text.splitlines()
              if 'custom_call_target="tpu_custom_call"' in ln]
    attends = [ln for ln in mosaic if "chunk_attention" in ln]
    # (a chunk that is not its prompt's last ends behind the last
    # attention op's write: nothing reads what that op would attend)
    assert len(attends) == (3 if name == "chunk_head" else 2)
    assert " while(" not in text
    has_head = any("ff.LINEAR.lm_head" in ln for ln in _buffers(text))
    assert has_head == (name == "chunk_head")
    assert mem.alias_size_in_bytes >= zaya_programs[1].pool.memory_bytes()
    assert mem.temp_size_in_bytes < 2 << 30


# ---- MiMo-V2.5: keys of 192 beside values of 128, a sink, two head counts -----

@pytest.fixture(scope="module")
def mimo_programs(one_chip):
    """The decode step and both chunk programs of one dense full layer,
    one windowed and one full expert layer at MiMo-V2.5's published
    widths and its cell's sizes (32 slots, contexts to 33,792, blocks of
    64, chunks of 2,048, 16 of 256 experts held, an eighth of the
    vocabulary), compiled for the described chip: {name: (the compiled
    text, its memory analysis)}, and the decoder."""
    import json
    import os

    from jax.experimental.compilation_cache import compilation_cache

    from benchmark.families import mimo as family
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.core.machine import make_mesh
    from flexflow_tpu.ffconst import CompMode
    from flexflow_tpu.serving.generation import PagedDecoder
    from flexflow_tpu.serving.kv_cache import Addresses

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "mimo-v2.5-ep16.json")) as f:
        config = json.load(f)
    config = dict(config, num_hidden_layers=3, hybrid_layer_pattern=[0, 1, 0],
                  moe_layer_freq=[0, 1, 1])
    slots, max_length, chunk = 32, 33792, 2048
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
            ff = FFModel(FFConfig(batch_size=slots, compute_dtype="bfloat16",
                                  ledger="off", search_cache="off",
                                  computation_mode=CompMode.INFERENCE))
            family.build(ff, config, slots, max_length)
            ff.compile(optimizer=None, loss_type=None, metrics=[],
                       mesh=make_mesh(devices=jax.devices()[:1]))
            dec = PagedDecoder(ff, max_length, decode_slots=slots,
                               block_size=64, kv_dtype="bfloat16",
                               calibrate=False, prefill_chunk=chunk)

            def on_chip(a):
                return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=one_chip)

            def ints(*shape, dtype=jnp.int32):
                return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

            params = jax.tree_util.tree_map(on_chip, dec._params_sds())
            pool = jax.tree_util.tree_map(on_chip, dec.pool.kv)
            acc = jax.tree_util.tree_map(on_chip, dec._expert_acc)
            mb = dec.max_blocks_per_request
            out = {}
            compiled = dec._decode.lower(
                params, ints(slots), pool,
                Addresses(ints(slots, mb), ints(slots)), ints(slots), acc,
                ints(slots), ints(slots, dtype=jnp.bool_)).compile()
            out["decode"] = (compiled.as_text(), compiled.memory_analysis())
            for name, head in (("chunk", False), ("chunk_head", True)):
                compiled = jax.jit(
                    lambda *a, head=head: dec._chunk_step(*a, head=head),
                    donate_argnums=(2,)).lower(
                    params, ints(1, chunk), pool,
                    Addresses(ints(1, mb), ints(1)), ints(1),
                    ints(1)).compile()
                out[name] = (compiled.as_text(), compiled.memory_analysis())
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return out, dec


def test_mimo_decode_step_reads_both_kinds_by_the_kernel(mimo_programs):
    """The decode step of full layers (64 query heads on 4 key-value heads,
    16 a group) and a windowed one (64 on 8, a ring of two blocks behind a
    sink) at keys of 192 beside values of 128: no ``while``, no
    ``conditional``; the paged kernel once an attention layer, both kinds
    (``attention_path`` says ``kernel`` of each); the arenas are two
    widths, the keys' rows 192 a head and not 256: nothing is padded in
    HBM; the only scatters are the new token's keys and values; the pool
    aliases its outputs."""
    programs, dec = mimo_programs
    text, mem = programs["decode"]
    assert dec.attention_path == {
        "decode": "kernel", "chunk": "kernel", "decode_chunk_tokens": 128}
    assert dec.attention_path_by_entry == {
        "pair": {"decode": "kernel", "chunk": "kernel"},
        "window": {"decode": "kernel", "chunk": "kernel"}}
    assert " while(" not in text and " conditional(" not in text
    for ln in text.splitlines():
        if " scatter(" in ln:
            assert re.search(r"ff\.MULTIHEAD_ATTENTION\.block\d_attn/write",
                             ln), ln
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert sum("paged_attention_decode" in c for c in calls) == 3
    assert sum("grouped_experts" in c for c in calls) == 2
    shapes = {name: tuple(a.shape for a in e)
              for name, e in dec.pool.kv.items()}
    assert shapes["block0_attn"] == shapes["block2_attn"] == (
        (32 * 528 + 1, 64, 4 * 192), (32 * 528 + 1, 64, 4 * 128))
    assert shapes["block1_attn"] == ((33 * 2, 64, 8 * 192),
                                     (33 * 2, 64, 8 * 128))
    # 5,120 B a token in the paged pool at the cell's two full layers
    assert dec.pool.kinds["block0_attn"].token_bytes(jnp.bfloat16) == 2560
    assert dec.pool.kinds["block1_attn"].request_bytes(jnp.bfloat16) \
        == 128 * 8 * 320 * 2
    assert mem.alias_size_in_bytes >= dec.pool.memory_bytes()


@pytest.mark.parametrize("name", ["chunk", "chunk_head"])
def test_mimo_chunk_programs_hold_no_square(mimo_programs, name):
    """A chunk of 2,048 queries over up to 33,792 keys: the attention of
    each layer that attends is ONE Mosaic call, ``chunk_attention``, its
    scores in VMEM (no ``while``: the span walk is not taken at a key
    width of one and a half lane tiles), named ``attend`` in a full layer
    and ``window`` inside ``attend`` in the windowed one."""
    from flexflow_tpu.core.op import parse_scope

    text, mem = mimo_programs[0][name]
    mosaic = [ln for ln in text.splitlines()
              if 'custom_call_target="tpu_custom_call"' in ln]
    attends = [ln for ln in mosaic if "chunk_attention" in ln]
    # (a chunk that is not its prompt's last ends behind the last
    # attention op's write: nothing reads what that op would attend)
    assert len(attends) == (3 if name == "chunk_head" else 2)
    assert " while(" not in text
    assert {parse_scope(re.search(r'op_name="([^"]+)"', ln).group(1))[2]
            for ln in attends} == {("attend",), ("attend", "window")}
    owners = {parse_scope(m) for m in re.findall(r'op_name="([^"]+)"', text)
              } - {None}
    subs = {(kind, s) for kind, _, ss, _ in owners for s in ss}
    assert {("MULTIHEAD_ATTENTION", "window"),
            ("MULTIHEAD_ATTENTION", "attend"),
            ("MULTIHEAD_ATTENTION", "project"),
            ("MULTIHEAD_ATTENTION", "write"),
            ("ROUTED_EXPERTS", "route"),
            ("ROUTED_EXPERTS", "experts")} <= subs, subs
    assert mem.temp_size_in_bytes < 2 << 30


@pytest.fixture(scope="module")
def ling_programs(one_chip):
    """The decode step and the two wider prefills (buckets of 1,024 and
    768) of two KDA layers and the
    latent layer (published layers 3, 4, 5, all with experts) at
    Ling-3.0-flash's published widths and its cell's sizes (256 slots,
    contexts to 4,096, blocks of 16, 64 of 512 experts held, an eighth of
    the vocabulary), compiled for the described chip: {name: (the compiled
    text, its memory analysis)}, and the decoder."""
    import json
    import os

    from jax.experimental.compilation_cache import compilation_cache

    from benchmark.families import ling as family
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.core.machine import make_mesh
    from flexflow_tpu.ffconst import CompMode
    from flexflow_tpu.serving.generation import PagedDecoder
    from flexflow_tpu.serving.kv_cache import Addresses

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "ling-3.0-flash-ep8.json")) as f:
        config = json.load(f)
    config = dict(config, first_layer=3, num_hidden_layers=3)
    slots, max_length = 256, 4096
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
            ff = FFModel(FFConfig(batch_size=slots, compute_dtype="bfloat16",
                                  ledger="off", search_cache="off",
                                  computation_mode=CompMode.INFERENCE))
            family.build(ff, config, slots, max_length)
            ff.compile(optimizer=None, loss_type=None, metrics=[],
                       mesh=make_mesh(devices=jax.devices()[:1]))
            dec = PagedDecoder(ff, max_length, decode_slots=slots,
                               block_size=16, kv_dtype="bfloat16",
                               calibrate=False, prefill_buckets=[768, 1024])

            def on_chip(a):
                return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=one_chip)

            def ints(*shape, dtype=jnp.int32):
                return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

            params = jax.tree_util.tree_map(on_chip, dec._params_sds())
            pool = jax.tree_util.tree_map(on_chip, dec.pool.kv)
            acc = jax.tree_util.tree_map(on_chip, dec._expert_acc)
            mb = dec.max_blocks_per_request
            out = {}
            compiled = dec._decode.lower(
                params, ints(slots), pool,
                Addresses(ints(slots, mb), ints(slots)), ints(slots), acc,
                ints(slots), ints(slots, dtype=jnp.bool_)).compile()
            out["decode"] = (compiled.as_text(), compiled.memory_analysis())
            for name, bucket in (("prefill", 1024), ("prefill_768", 768)):
                compiled = jax.jit(
                    dec._prefill_step, donate_argnums=(2,)).lower(
                    params, ints(1, bucket), pool,
                    Addresses(ints(1, mb), ints(1)), ints(1)).compile()
                out[name] = (compiled.as_text(), compiled.memory_analysis())
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return out, dec


def test_ling_decode_step_steps_both_kinds_by_their_kernels(ling_programs):
    """The decode step of two KDA layers and a latent layer at 256 slots:
    both kinds read in place (``attention_path`` ``kernel``): a
    ``gated_delta_decode`` call a KDA layer, under the op's ``rule``, each
    the only maker of its state arena (257 x 128 x 4,096 float32, 539 MB;
    aliased through), a ``state_tails_step`` call a KDA layer under its
    ``conv`` (since PR 59 the step has no ``write`` of tails: the kernel
    shifts them where they lie), ONE ``latent_attention_decode`` at 32
    heads, and the
    grouped experts' kernel an expert layer with no ``conditional`` (256
    rows are past the count at which a step's form is the kernel's
    whatever it names); no loop over the slots, and nothing beside the
    weights and the pool but 256 MB (136 when written)."""
    from flexflow_tpu.core.op import parse_scope

    programs, dec = ling_programs
    text, mem = programs["decode"]
    assert dec.attention_path["decode"] == "kernel"
    assert dec.attention_path_by_entry == {
        "state": {"decode": "kernel", "chunk": None},
        "latent": {"decode": "kernel", "chunk": None}}
    assert " while(" not in text
    assert " conditional(" not in text
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert sum("gated_delta_decode" in ln for ln in calls) == 2
    assert sum("state_tails_step" in ln for ln in calls) == 2
    assert sum("latent_attention_decode" in ln for ln in calls) == 1
    assert sum("grouped_experts" in ln for ln in calls) == 3
    for ln in calls:
        if "gated_delta_decode" in ln:
            (scope,) = re.findall(r'op_name="([^"]+)"', ln)
            assert parse_scope(scope)[0] == "KIMI_DELTA_ATTENTION"
            assert parse_scope(scope)[2] == ("rule",)
            assert "f32[257,128,4096]" in ln
    owners = {parse_scope(m) for m in re.findall(r'op_name="([^"]+)"', text)
              } - {None}
    subs = {(kind, sub) for kind, _, subs_, _ in owners for sub in subs_}
    assert {("KIMI_DELTA_ATTENTION", "project"),
            ("KIMI_DELTA_ATTENTION", "conv"),
            ("KIMI_DELTA_ATTENTION", "gate"),
            ("KIMI_DELTA_ATTENTION", "rule"),
            ("KIMI_DELTA_ATTENTION", "out"),
            ("LATENT_ATTENTION", "attend"), ("LATENT_ATTENTION", "gate"),
            ("ROUTED_EXPERTS", "route"),
            ("ROUTED_EXPERTS", "experts")} <= subs, subs
    assert mem.temp_size_in_bytes < 256 << 20
    assert mem.alias_size_in_bytes >= dec.pool.memory_bytes()


def _rule_kernels(text):
    """The Mosaic calls of the whole-sequence rule with a decay a key
    channel in a compiled program, each under a KDA op's ``rule``."""
    from flexflow_tpu.core.op import parse_scope

    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln
             and "channel_delta_chunks" in ln]
    for ln in calls:
        (scope,) = re.findall(r'op_name="([^"]+)"', ln)
        assert parse_scope(scope)[0] == "KIMI_DELTA_ATTENTION"
        assert parse_scope(scope)[2] == ("rule",)
    return calls


@pytest.mark.parametrize("name", ["prefill", "prefill_768"])
def test_ling_prefill_runs_the_channel_rule_as_one_kernel(
        monkeypatch, ling_programs, name):
    """A prefill of 1,024 and of 768 tokens: the per-channel rule is ONE
    ``channel_delta_chunks`` call a KDA layer, under the op's ``rule``
    (since PR 64; the jnp form's walks over its chunks and its system's
    rows were the program's only loops), no ``while`` anywhere, the
    name of no other reader's kernel (``gated_delta_decode``) in it, the
    experts the grouped kernel, and its temporaries stay under 3 GB
    beside a pool that fills the chip. The kind says so
    (``prefill_path``), which ``stats()["kv"]["state"]`` hands on."""
    programs, dec = ling_programs
    text, mem = programs[name]
    assert len(_rule_kernels(text)) == 2
    assert "gated_delta_decode" not in text
    assert " while(" not in text
    assert dec.prefill_path == "kernel"
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
    assert {kind.prefill_path(bucket) for kind in dec.pool.kinds.values()
            for bucket in (512, 768, 1024)} == {"kernel", None}
    assert mem.temp_size_in_bytes < 3 << 30


@pytest.mark.parametrize("name,rows", [("decode", 256), ("prefill", 1024)])
def test_ling_routers_sort_their_groups_and_nothing_else(ling_programs,
                                                         name, rows):
    """Since PR 62 a router picks by passes where ``select_form`` says so:
    of the three picks of Ling's ``route`` (the best 2 of each group of
    64, 4 groups of 8, 8 experts of 512) the compiled programs sort the
    groups' eight scores alone, once an expert layer, and scatter nothing
    (the kept groups are a compare)."""
    routed = [ln for ln in ling_programs[0][name][0].splitlines()
              if "/route/" in ln]
    sorts = [ln for ln in routed if " sort(" in ln]
    assert len(sorts) == 3, sorts
    for ln in sorts:
        assert f"f32[{rows},8]" in ln and "512]" not in ln, ln
    assert not [ln for ln in routed if " scatter(" in ln]


@pytest.fixture(scope="module")
def glm_programs(one_chip):
    """The decode step and both chunk programs of a KDA layer with the
    dense MLP and the sparse latent layer with experts (published layers 2
    and 3) at GLM-5.3-Flash's published widths and its cell's sizes (32
    slots, contexts to 66,560, blocks of 64, chunks of 2,048, 36 of 288
    experts held, an eighth of the vocabulary, four residual streams),
    compiled for the described chip over a pool of two slots' blocks (the
    tables are the cell's): {name: (the compiled text, its memory
    analysis)}, and the decoder."""
    import json
    import os

    from jax.experimental.compilation_cache import compilation_cache

    from benchmark.families import glm as family
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.core.machine import make_mesh
    from flexflow_tpu.ffconst import CompMode
    from flexflow_tpu.serving.generation import PagedDecoder
    from flexflow_tpu.serving.kv_cache import Addresses

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "glm-5.3-flash-ep8.json")) as f:
        config = json.load(f)
    config = dict(config, num_hidden_layers=2,
                  **{k: config[k][:2] for k in (
                      "layer_types", "mlp_layer_types", "indexer_types")})
    slots, max_length, chunk = 32, 66560, 2048
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("FLEXFLOW_TPU_PALLAS", "compiled")
            ff = FFModel(FFConfig(batch_size=slots, compute_dtype="bfloat16",
                                  ledger="off", search_cache="off",
                                  computation_mode=CompMode.INFERENCE))
            family.build(ff, config, slots, max_length)
            ff.compile(optimizer=None, loss_type=None, metrics=[],
                       mesh=make_mesh(devices=jax.devices()[:1]))
            dec = PagedDecoder(ff, max_length, decode_slots=slots,
                               block_size=64, kv_dtype="bfloat16",
                               calibrate=False, prefill_chunk=chunk,
                               num_blocks=2 * 1040 + 1)

            def on_chip(a):
                return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=one_chip)

            def ints(*shape, dtype=jnp.int32):
                return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

            params = jax.tree_util.tree_map(on_chip, dec._params_sds())
            pool = jax.tree_util.tree_map(on_chip, dec.pool.kv)
            acc = jax.tree_util.tree_map(on_chip, dec._expert_acc)
            mb = dec.max_blocks_per_request
            out = {}
            compiled = dec._decode.lower(
                params, ints(slots), pool,
                Addresses(ints(slots, mb), ints(slots)), ints(slots), acc,
                ints(slots), ints(slots, dtype=jnp.bool_)).compile()
            out["decode"] = (compiled.as_text(), compiled.memory_analysis())
            for name, head in (("chunk", False), ("chunk_head", True)):
                compiled = jax.jit(
                    lambda *a, head=head: dec._chunk_step(*a, head=head),
                    donate_argnums=(2,)).lower(
                    params, ints(1, chunk), pool,
                    Addresses(ints(1, mb), ints(1)), ints(1),
                    ints(1)).compile()
                out[name] = (compiled.as_text(), compiled.memory_analysis())
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return out, dec


def test_glm_decode_step_selects_gathers_and_keeps_its_kernels(glm_programs):
    """The decode step of a KDA layer and the sparse latent layer at 32
    slots behind four residual streams: the state kind by its kernels
    (``gated_delta_decode`` under ``rule``, ``state_tails_step`` under
    ``conv``: the program's verdict is ``kernel``, the sparse latent kind
    has one form and says ``gather`` of itself), the indexer's scores and
    top-k under ``select`` and the gather of 512 pools of four rows, a
    slice a pool, under ``attend``, the experts' kernel, the stream mixes
    under ``mix``; no ``conditional``, no array of every slot's whole
    context, no copy of the arena, and nothing beside the weights and the
    pool but half a gigabyte."""
    from flexflow_tpu.core.op import parse_scope

    programs, dec = glm_programs
    text, mem = programs["decode"]
    assert dec.attention_path["decode"] == "kernel"
    assert dec.attention_path_by_entry == {
        "state": {"decode": "kernel", "chunk": "kernel"},
        "sparse_latent": {"decode": "gather", "chunk": "scan"}}
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert sum("gated_delta_decode" in ln for ln in calls) == 1
    assert sum("state_tails_step" in ln for ln in calls) == 1
    assert sum("latent_attention_decode" in ln for ln in calls) == 0
    assert sum("grouped_experts" in ln for ln in calls) == 1
    assert " conditional(" not in text
    owners = {parse_scope(m) for m in re.findall(r'op_name="([^"]+)"', text)
              } - {None}
    subs = {(kind, sub) for kind, _, subs_, _ in owners for sub in subs_}
    assert {("KIMI_DELTA_ATTENTION", "rule"), ("KIMI_DELTA_ATTENTION", "conv"),
            ("KIMI_DELTA_ATTENTION", "gate"), ("KIMI_DELTA_ATTENTION", "out"),
            ("LATENT_ATTENTION", "project"), ("LATENT_ATTENTION", "select"),
            ("LATENT_ATTENTION", "attend"), ("LATENT_ATTENTION", "write"),
            ("STREAM_MIX", "mix"), ("ROUTED_EXPERTS", "route"),
            ("ROUTED_EXPERTS", "experts")} <= subs, subs
    # the arena by pools, as it lies: never copied into another layout
    assert not re.search(r"bf16\[2081,16,2048\]\{(?!2,1,0)", text)
    assert "bf16[32,66560,512]" not in text
    assert mem.temp_size_in_bytes < 512 << 20
    assert mem.alias_size_in_bytes >= dec.pool.memory_bytes()


@pytest.mark.parametrize("name", ["chunk", "chunk_head"])
def test_glm_chunk_programs_carry_state_and_selection(glm_programs, name):
    """A chunk of 2,048 tokens: the KDA layer continues from the request's
    state and tails by the per-channel rule as ONE ``channel_delta_chunks``
    call under its ``rule`` (since PR 64: no ``while`` of the op's is
    left), the sparse latent layer scores its
    queries, picks and gathers their taken rows a tile of 128 queries at a
    time under ONE ``conditional`` (a first chunk inside the dense regime
    walks its key spans causally instead); every loop lies under one of
    those ops; the arena lies by pools and is never copied into another
    layout (over rows a token a gather of whole pools made the compiler do
    that, 2.2 GB a call); no (heads,
    queries, context) array and no (queries, heads, pools) one of the
    whole chunk is made, and the temporaries stay under 1.5 GB beside a
    pool that fills the chip."""
    programs, dec = glm_programs
    text, mem = programs[name]
    assert len(_rule_kernels(text)) == 1
    assert "gated_delta_decode" not in text
    for ln in text.splitlines():
        if " while(" in ln:
            assert ("ff.LATENT_ATTENTION." in ln) or (
                "ff.ROUTED_EXPERTS." in ln), ln
    assert text.count(" conditional(") >= 1
    assert not re.search(r"bf16\[2081,16,2048\]\{(?!2,1,0)", text)
    assert "f32[1,64,2048,66560]" not in text
    assert "f32[1,2048,32,16640]" not in text
    assert mem.temp_size_in_bytes < 1536 << 20
