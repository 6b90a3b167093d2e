"""Pipeline-parallel tests (no reference analog — PP is reserved but
unimplemented upstream, model.h:190-192; SURVEY.md §2.3/§7 step 10).

Runs GPipe over a pipe×data mesh on the hermetic 8-device CPU platform and
checks numerical equivalence against non-pipelined training.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu import FFConfig, FFModel, LossType, MetricsType
from flexflow_tpu.parallel.pipeline import PipelineConfig, split_stages
from flexflow_tpu.runtime.optimizer import SGDOptimizer


def _build(ff, bs):
    x = ff.create_tensor((bs, 16), name="input")
    h = ff.dense(x, 32, name="fc1")
    h = ff.relu(h, name="act1")
    h = ff.dense(h, 32, name="fc2")
    h = ff.relu(h, name="act2")
    h = ff.dense(h, 4, name="head")
    return ff.softmax(h, name="probs")


def _data(n=64, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 16)).astype(np.float32)
    w = rng.normal(size=(16, 4)).astype(np.float32)
    y = np.argmax(x @ w, axis=1).astype(np.int32).reshape(-1, 1)
    return x, y


def test_split_stages_tail_heavy_stays_contiguous():
    """Repair of thin stages must shift boundaries, never reorder ops
    (round-1 advisor finding: FLOPs [1,1,5] over 3 stages yielded
    [[a],[c],[b]], executing b after its consumer c)."""

    class FakeOp:
        def __init__(self, name, f):
            self.name, self._f = name, f

        def flops(self):
            return self._f

    ops = [FakeOp("a", 1.0), FakeOp("b", 1.0), FakeOp("c", 5.0)]
    stages = split_stages(ops, 3)
    assert [[o.name for o in st] for st in stages] == [["a"], ["b"], ["c"]]
    # heavier tail, more shapes
    ops = [FakeOp(f"o{i}", f) for i, f in enumerate([1, 1, 1, 1, 100, 100])]
    for S in (2, 3, 4, 5, 6):
        stages = split_stages(ops, S)
        assert all(stages), f"empty stage with S={S}"
        flat = [o.name for st in stages for o in st]
        assert flat == [o.name for o in ops], f"reordered with S={S}"


def test_split_stages_balanced_and_contiguous():
    ff = FFModel(FFConfig(batch_size=8, seed=0))
    _build(ff, 8)
    ff.compile(optimizer=SGDOptimizer(lr=0.1),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY, metrics=[])
    stages = split_stages(ff.compiled.ops, 2)
    assert len(stages) == 2 and all(stages)
    flat = [op.name for st in stages for op in st]
    assert flat == [op.name for op in ff.compiled.ops]  # contiguous order


def test_pipeline_matches_single_device_training():
    bs = 16
    x, y = _data(n=bs)  # one batch per epoch: deterministic comparison

    def run(pipelined):
        ff = FFModel(FFConfig(
            batch_size=bs, epochs=3, seed=0,
            mesh_shape={"pipe": 2, "data": 4} if pipelined else {"data": 8},
        ))
        _build(ff, bs)
        kw = dict(pipeline=PipelineConfig(num_stages=2, num_microbatches=4)) \
            if pipelined else {}
        ff.compile(optimizer=SGDOptimizer(lr=0.1),
                   loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                   metrics=[MetricsType.ACCURACY], **kw)
        hist = ff.fit(x, y, verbose=False, shuffle=False)
        if pipelined:
            params = ff.pipelined.all_params()
        else:
            params = ff.compiled.params
        return hist, {k: {w: np.asarray(v) for w, v in ws.items()}
                      for k, ws in params.items()}

    h_pp, p_pp = run(True)
    h_sd, p_sd = run(False)
    # identical data, seed, optimizer: GPipe with grad accumulation equals
    # full-batch training up to float tolerance
    for name in p_sd:
        for w in p_sd[name]:
            np.testing.assert_allclose(
                p_pp[name][w], p_sd[name][w], rtol=2e-4, atol=2e-5,
                err_msg=f"{name}/{w}",
            )
    assert abs(h_pp[-1].accuracy - h_sd[-1].accuracy) <= 0.15


@pytest.mark.parametrize("microbatches", [2, 4])
def test_pipeline_step_dispatches_counted(microbatches):
    """What a pipelined step costs the host, as a count that repeats: the
    host engine dispatches once a forward, a backward and an accumulation
    a stage and microbatch and once an update a stage, (3 S - 1) M in all
    for S stages of GPipe over M microbatches; the compiled engine
    dispatches ONE schedule program behind its input placements, whatever
    M. Read from the engines' own ``step_dispatches``; no clock (the
    wall-clock ratio of two CPU runs this replaces measured the box, not
    the engine: ROADMAP D2)."""
    stages = 2
    host, _, _ = _train_variant("gpipe", engine="host",
                                mesh_shape={"pipe": stages}, steps=1,
                                num_microbatches=microbatches)
    assert host.pipelined.engine_name == "host"
    assert host.pipelined.step_dispatches \
        == (3 * stages - 1) * microbatches
    compiled, _, _ = _train_variant("gpipe", engine="auto",
                                    mesh_shape={"pipe": stages}, steps=1,
                                    num_microbatches=microbatches)
    assert compiled.pipelined.engine_name == "compiled"
    # the tokens' and the labels' placements, then the one program
    assert compiled.pipelined.step_dispatches == 3


def test_pipeline_forward_only():
    bs = 8
    ff = FFModel(FFConfig(batch_size=bs, seed=0, mesh_shape={"pipe": 2, "data": 4}))
    _build(ff, bs)
    ff.compile(optimizer=SGDOptimizer(lr=0.1),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[], pipeline=PipelineConfig(num_stages=2,
                                                   num_microbatches=2))
    x, _ = _data(n=bs)
    out = np.asarray(ff.pipelined.forward_only([jnp.asarray(x)]))
    assert out.shape == (bs, 4)
    np.testing.assert_allclose(out.sum(axis=-1), 1.0, rtol=1e-5)


def test_pipeline_momentum_matches_single_device():
    """Optimizer state must accumulate correctly per stage: momentum-SGD
    pipelined training equals non-pipelined training."""
    bs = 16
    x, y = _data(n=bs)

    def run(pipelined):
        ff = FFModel(FFConfig(
            batch_size=bs, epochs=4, seed=0,
            mesh_shape={"pipe": 2, "data": 4} if pipelined else {"data": 8},
        ))
        _build(ff, bs)
        kw = dict(pipeline=PipelineConfig(num_stages=2, num_microbatches=4)) \
            if pipelined else {}
        ff.compile(optimizer=SGDOptimizer(lr=0.1, momentum=0.9),
                   loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                   metrics=[], **kw)
        ff.fit(x, y, verbose=False, shuffle=False)
        params = ff.pipelined.all_params() if pipelined else ff.compiled.params
        return {k: {w: np.asarray(v) for w, v in ws.items()}
                for k, ws in params.items()}

    p_pp, p_sd = run(True), run(False)
    for name in p_sd:
        for w in p_sd[name]:
            np.testing.assert_allclose(
                p_pp[name][w], p_sd[name][w], rtol=5e-4, atol=5e-5,
                err_msg=f"{name}/{w}")


def test_pipelined_checkpoint_roundtrips_opt_state(tmp_path):
    """sync_to must carry optimizer state into cm (round-1 advisor: a
    checkpoint after a pipelined fit recorded untouched initial state), and
    restore must re-seed the pipeline's per-stage state."""
    bs = 16
    x, y = _data(n=64)

    def make():
        ff = FFModel(FFConfig(batch_size=bs, epochs=2, seed=0,
                              mesh_shape={"pipe": 2, "data": 4}))
        _build(ff, bs)
        ff.compile(optimizer=SGDOptimizer(lr=0.1, momentum=0.9),
                   loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                   metrics=[],
                   pipeline=PipelineConfig(num_stages=2, num_microbatches=4))
        return ff

    ff = make()
    ff.fit(x, y, verbose=False, shuffle=False)
    # sync_to ran inside fit: cm.opt_state now holds real momenta
    mom = {k: {w: np.asarray(v) for w, v in ws.items()}
           for k, ws in ff.compiled.opt_state.items()}
    assert any(np.abs(v).max() > 0 for ws in mom.values() for v in ws.values()), \
        "cm.opt_state still zeros after pipelined fit"
    ff.save_checkpoint(str(tmp_path / "ck"), step=1)

    ff2 = make()
    ff2.load_checkpoint(str(tmp_path / "ck"))
    # per-stage state must match what was saved
    for s, sp in enumerate(ff2.pipelined.stage_params):
        for op_name in sp:
            for w, v in ff2.pipelined.stage_opt_state[s][op_name].items():
                np.testing.assert_allclose(
                    np.asarray(v), mom[op_name][w], rtol=1e-6,
                    err_msg=f"stage{s} {op_name}/{w}")


def test_pipelined_fit_syncs_compiled_params(tmp_path):
    """Checkpoint/eval after a pipelined fit must see trained weights."""
    bs = 16
    x, y = _data(n=64)
    ff = FFModel(FFConfig(batch_size=bs, epochs=3, seed=0,
                          mesh_shape={"pipe": 2, "data": 4}))
    _build(ff, bs)
    ff.compile(optimizer=SGDOptimizer(lr=0.2),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.ACCURACY],
               pipeline=PipelineConfig(num_stages=2, num_microbatches=4))
    before = {k: {w: np.asarray(v) for w, v in ws.items()}
              for k, ws in ff.compiled.params.items()}
    ff.fit(x, y, verbose=False)
    after = ff.compiled.params
    changed = any(
        not np.allclose(before[k][w], np.asarray(after[k][w]))
        for k in before for w in before[k]
    )
    assert changed, "cm.params not synced after pipelined fit"
    ff.save_checkpoint(str(tmp_path / "ck"), step=1)  # saves trained weights


def test_moe_graph_pipelines():
    """MoE through the GPipe engine: aggregate ops must derive batch from
    the RUNTIME microbatch, not the compiled batch (a static reshape
    silently folded tokens into features — AE round-3 regression)."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu import (FFConfig, FFModel, LossType, SGDOptimizer,
                              make_mesh)
    from flexflow_tpu.models import MoeConfig, build_moe_mnist
    from flexflow_tpu.parallel.pipeline import PipelineConfig

    ff = FFModel(FFConfig(batch_size=16, seed=0))
    build_moe_mnist(ff, 16, MoeConfig(input_dim=32, num_classes=4,
                                      num_exp=4, num_select=2,
                                      expert_hidden_size=16, alpha=2.0))
    mesh = make_mesh({"pipe": 2, "data": 2},
                     devices=jax.devices("cpu")[:4])
    ff.compile(optimizer=SGDOptimizer(lr=0.05),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[], mesh=mesh,
               pipeline=PipelineConfig(num_stages=2, num_microbatches=2))
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(16, 32)).astype(np.float32)
    ys = rng.integers(0, 4, size=(16, 1)).astype(np.int32)
    losses = []
    for i in range(3):
        loss, _ = ff.pipelined.train_step(
            jax.random.key(i), [jnp.asarray(xs)], jnp.asarray(ys))
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]  # actually learning, not reshuffled junk


# ------------------------------------------------------------------- #
# schedule/engine equivalence + satellite regressions (PR 4)          #
# ------------------------------------------------------------------- #
def _train_variant(schedule, engine="host", interleave=1, remat=False,
                   mesh_shape=None, steps=3, momentum=0.9,
                   num_microbatches=4):
    """Train the 3-dense model for a few steps under one
    (schedule, engine) variant; returns (losses, params)."""
    bs = 16
    x, y = _data(n=bs)
    ff = FFModel(FFConfig(batch_size=bs, seed=0))
    mesh = None
    if mesh_shape is None:
        mesh_shape = {"pipe": 2, "data": 4}
    from flexflow_tpu import make_mesh

    n = 1
    for v in mesh_shape.values():
        n *= v
    mesh = make_mesh(mesh_shape, devices=jax.devices()[:n])
    _build(ff, bs)
    ff.compile(optimizer=SGDOptimizer(lr=0.1, momentum=momentum),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[], mesh=mesh,
               pipeline=PipelineConfig(
                   num_stages=2, num_microbatches=num_microbatches,
                   schedule=schedule, engine=engine,
                   interleave=interleave, remat=remat))
    losses = []
    for i in range(steps):
        loss, _ = ff.pipelined.train_step(
            jax.random.key(i), [jnp.asarray(x)], jnp.asarray(y))
        losses.append(loss)
    params = {k: {w: np.asarray(v) for w, v in ws.items()}
              for k, ws in ff.pipelined.all_params().items()}
    return ff, losses, params


def test_schedules_bit_identical_on_composite_mesh():
    """1F1B / interleaved / remat reorder work, never math: on the
    pipe x data mesh every schedule's per-step losses and trained params
    equal the historical GPipe path bit for bit (same per-stage
    microbatch accumulation order, same per-(mb, chunk) rng keys)."""
    _, l_ref, p_ref = _train_variant("gpipe")
    for kw in (dict(schedule="1f1b"),
               dict(schedule="1f1b", remat=True),
               dict(schedule="interleaved", interleave=2)):
        _, l, p = _train_variant(**kw)
        assert l == l_ref, (kw, l, l_ref)
        for k in p_ref:
            for w in p_ref[k]:
                np.testing.assert_array_equal(
                    p[k][w], p_ref[k][w], err_msg=f"{kw} {k}/{w}")


def test_compiled_engine_bit_identical_and_single_dispatch():
    """The single-dispatch engine: ONE jitted program per train step
    (O(1) dispatches vs O(stages x microbatches)), numerically identical
    to the host-driven sync GPipe path on the same pipe-only mesh."""
    ff_ref, l_ref, p_ref = _train_variant(
        "gpipe", engine="host", mesh_shape={"pipe": 2})
    assert ff_ref.pipelined.engine_name == "host"
    host_disp = ff_ref.pipelined.step_dispatches
    for schedule in ("gpipe", "1f1b"):
        ff, l, p = _train_variant(
            schedule, engine="auto", mesh_shape={"pipe": 2})
        pm = ff.pipelined
        assert pm.engine_name == "compiled", schedule
        assert pm.step_dispatches < host_disp
        assert pm.step_dispatches <= 3  # 1 program + input placements
        assert l == l_ref, (schedule, l, l_ref)
        for k in p_ref:
            for w in p_ref[k]:
                np.testing.assert_array_equal(
                    p[k][w], p_ref[k][w], err_msg=f"{schedule} {k}/{w}")
    # forcing the compiled engine outside its envelope (a non-trivial
    # axis that is neither pipe nor data) raises with the reason instead
    # of silently running the wrong engine
    with pytest.raises(ValueError, match="families only"):
        _train_variant("1f1b", engine="compiled",
                       mesh_shape={"pipe": 2, "model": 2}, steps=0)


def test_compiled_engine_interleaved_bit_identical():
    """PR 12 tentpole (a): interleaved virtual stages inside the
    single-dispatch envelope — chunk round-robin rides the tick-table
    chunk/slot tables, losses/params bit-identical to the host engine,
    still O(1) dispatches."""
    ff_h, l_h, p_h = _train_variant(
        "interleaved", engine="host", interleave=2,
        mesh_shape={"pipe": 2})
    assert ff_h.pipelined.engine_name == "host"
    ff_c, l_c, p_c = _train_variant(
        "interleaved", engine="auto", interleave=2,
        mesh_shape={"pipe": 2})
    pm = ff_c.pipelined
    assert pm.engine_name == "compiled"
    assert pm.step_dispatches <= 3
    assert pm.step_dispatches < ff_h.pipelined.step_dispatches
    assert l_c == l_h, (l_c, l_h)
    for k in p_h:
        for w in p_h[k]:
            np.testing.assert_array_equal(p_c[k][w], p_h[k][w],
                                          err_msg=f"{k}/{w}")


def test_compiled_engine_pipe_data_submesh_bit_identical():
    """PR 12 tentpole (b): the pipe×data stage-submesh family — the
    compiled engine shard_maps over BOTH axes, psums each backward's
    gradient over data in host-engine order, and reduces the recorded
    local-mean losses once after the scan. Bit-identical to the host
    engine's GSPMD lowering on the same mesh, for plain and interleaved
    schedules."""
    for kw in (dict(schedule="1f1b"),
               dict(schedule="interleaved", interleave=2)):
        ff_h, l_h, p_h = _train_variant(
            engine="host", mesh_shape={"pipe": 2, "data": 2}, **kw)
        ff_c, l_c, p_c = _train_variant(
            engine="auto", mesh_shape={"pipe": 2, "data": 2}, **kw)
        pm = ff_c.pipelined
        assert pm.engine_name == "compiled", kw
        assert pm.step_dispatches <= 3
        assert pm.step_dispatches < ff_h.pipelined.step_dispatches
        assert l_c == l_h, (kw, l_c, l_h)
        for k in p_h:
            for w in p_h[k]:
                np.testing.assert_array_equal(
                    p_c[k][w], p_h[k][w], err_msg=f"{kw} {k}/{w}")


def test_compiled_engine_dp_batch_coupled_falls_back_with_reason():
    """A batch-coupled graph (MoE gating family) under a data submesh
    must stay host-driven — per-shard routing statistics would diverge
    from the GSPMD full-batch lowering — and the fallback must carry
    its reason into the profile (explain_run's silent-fallback gate)."""
    from flexflow_tpu import SGDOptimizer, make_mesh
    from flexflow_tpu.models import MoeConfig, build_moe_mnist

    ff = FFModel(FFConfig(batch_size=16, seed=0))
    build_moe_mnist(ff, 16, MoeConfig(input_dim=32, num_classes=4,
                                      num_exp=4, num_select=2,
                                      expert_hidden_size=16, alpha=2.0))
    mesh = make_mesh({"pipe": 2, "data": 2}, devices=jax.devices()[:4])
    ff.compile(optimizer=SGDOptimizer(lr=0.05),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[], mesh=mesh,
               pipeline=PipelineConfig(num_stages=2, num_microbatches=2,
                                       schedule="1f1b", engine="auto"))
    pm = ff.pipelined
    assert pm.engine_name == "host"
    assert "batch-coupled" in (pm.fallback_reason or "")
    rec = pm.profile()
    assert rec["fallback_reason"] == pm.fallback_reason
    assert rec["compiled_mesh_eligible"] is True
    # the same graph on a pipe-only mesh IS compiled-eligible (integer
    # routing tensors pack via bitcast; aux losses ride the (V, M) cells)
    ff2 = FFModel(FFConfig(batch_size=16, seed=0))
    build_moe_mnist(ff2, 16, MoeConfig(input_dim=32, num_classes=4,
                                       num_exp=4, num_select=2,
                                       expert_hidden_size=16, alpha=2.0))
    ff2.compile(optimizer=SGDOptimizer(lr=0.05),
                loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                metrics=[], mesh=make_mesh({"pipe": 2},
                                           devices=jax.devices()[:2]),
                pipeline=PipelineConfig(num_stages=2,
                                        num_microbatches=2,
                                        schedule="1f1b", engine="auto"))
    assert ff2.pipelined.engine_name == "compiled"


def test_sync_roundtrip_params_and_opt_state():
    """sync_to/sync_from round trip against the CompiledModel: params
    AND optimizer state (incl. the zero_optimizer sharded layout)
    survive engine -> cm -> fresh engine without drift."""
    bs = 16
    x, y = _data(n=bs)
    from flexflow_tpu import make_mesh

    def make(zero):
        ff = FFModel(FFConfig(batch_size=bs, seed=0, zero_optimizer=zero))
        _build(ff, bs)
        ff.compile(optimizer=SGDOptimizer(lr=0.1, momentum=0.9),
                   loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                   metrics=[], mesh=make_mesh({"pipe": 2, "data": 4}),
                   pipeline=PipelineConfig(num_stages=2,
                                           num_microbatches=4,
                                           schedule="1f1b"))
        return ff

    for zero in (False, True):
        ff = make(zero)
        for i in range(2):
            ff.pipelined.train_step(jax.random.key(i), [jnp.asarray(x)],
                                    jnp.asarray(y))
        pm = ff.pipelined
        trained = {k: {w: np.asarray(v) for w, v in ws.items()}
                   for k, ws in pm.all_params().items()}
        mom = [jax.tree.map(np.asarray, st) for st in pm.stage_opt_state]
        pm.sync_to(ff.compiled)
        # cm now holds the trained values (zero layout preserved)
        for k, ws in trained.items():
            for w, v in ws.items():
                np.testing.assert_array_equal(
                    np.asarray(ff.compiled.params[k][w]), v,
                    err_msg=f"zero={zero} {k}/{w}")
        # momentum is non-trivial after 2 steps
        assert any(np.abs(v).max() > 0
                   for st in mom for ws in st.values()
                   for v in ws.values())
        # fresh engine re-seeded from cm equals the trained engine
        pm.sync_from(ff.compiled)
        for s, st in enumerate(pm.stage_opt_state):
            got = jax.tree.map(np.asarray, st)
            for opn in mom[s]:
                for w in mom[s][opn]:
                    np.testing.assert_array_equal(
                        got[opn][w], mom[s][opn][w],
                        err_msg=f"zero={zero} stage{s} {opn}/{w}")
        for k, ws in trained.items():
            for w, v in ws.items():
                np.testing.assert_array_equal(
                    np.asarray(pm.all_params()[k][w]), v,
                    err_msg=f"zero={zero} resync {k}/{w}")


def test_grad_accum_composes_with_pipeline():
    """config.grad_accum_steps folds into the schedule's microbatch
    count: pipelined training with K-fold accumulation equals the
    single-mesh grad-accum path (same averaging) to float tolerance."""
    bs = 16
    x, y = _data(n=bs)
    from flexflow_tpu import make_mesh

    ff = FFModel(FFConfig(batch_size=bs, seed=0, grad_accum_steps=2))
    _build(ff, bs)
    ff.compile(optimizer=SGDOptimizer(lr=0.1),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[], mesh=make_mesh({"pipe": 2, "data": 4}),
               pipeline=PipelineConfig(num_stages=2, num_microbatches=2,
                                       schedule="1f1b"))
    assert ff.pipelined.cfg.num_microbatches == 4  # 2 x K
    for i in range(2):
        ff.pipelined.train_step(jax.random.key(i), [jnp.asarray(x)],
                                jnp.asarray(y))
    p_pp = {k: {w: np.asarray(v) for w, v in ws.items()}
            for k, ws in ff.pipelined.all_params().items()}

    ff2 = FFModel(FFConfig(batch_size=bs, seed=0, grad_accum_steps=4,
                           mesh_shape={"data": 8}))
    _build(ff2, bs)
    ff2.compile(optimizer=SGDOptimizer(lr=0.1),
                loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                metrics=[])
    cm = ff2.compiled
    xb = jax.device_put(x, cm.input_shardings[0])
    yb = jax.device_put(y, cm.label_sharding)
    for i in range(2):
        cm.params, cm.opt_state, _, _ = cm.train_step(
            cm.params, cm.opt_state, jax.random.key(i), xb, yb)
    for k in p_pp:
        for w in p_pp[k]:
            np.testing.assert_allclose(
                p_pp[k][w], np.asarray(cm.params[k][w]),
                rtol=2e-4, atol=2e-5, err_msg=f"{k}/{w}")


def test_lr_schedule_live_without_retrace():
    """Satellite: stage updates take optimizer hyperparams as TRACED
    arguments, so set_learning_rate is live on the NEXT step without
    rebuilding any jitted update (refresh_updates is a no-op hook)."""
    bs = 16
    x, y = _data(n=bs)
    from flexflow_tpu import make_mesh

    def make(engine):
        ff = FFModel(FFConfig(batch_size=bs, seed=0))
        _build(ff, bs)
        shape = {"pipe": 2} if engine == "compiled" else \
            {"pipe": 2, "data": 4}
        n = 2 if engine == "compiled" else 8
        ff.compile(optimizer=SGDOptimizer(lr=0.1),
                   loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                   metrics=[],
                   mesh=make_mesh(shape, devices=jax.devices()[:n]),
                   pipeline=PipelineConfig(num_stages=2,
                                           num_microbatches=4,
                                           schedule="1f1b",
                                           engine=engine))
        return ff

    for engine in ("host", "compiled"):
        ff = make(engine)
        pm = ff.pipelined
        updates_before = list(getattr(pm, "_stage_update", []))
        pm.train_step(jax.random.key(0), [jnp.asarray(x)], jnp.asarray(y))
        ff.set_learning_rate(1e-6)  # ~freezes training if honored
        assert list(getattr(pm, "_stage_update", [])) == updates_before, \
            "set_learning_rate rebuilt the jitted stage updates"
        before = {k: {w: np.asarray(v) for w, v in ws.items()}
                  for k, ws in pm.all_params().items()}
        pm.train_step(jax.random.key(1), [jnp.asarray(x)], jnp.asarray(y))
        after = pm.all_params()
        max_delta = max(
            np.abs(before[k][w] - np.asarray(after[k][w])).max()
            for k in before for w in before[k])
        assert max_delta < 1e-4, (
            f"{engine}: lr change not live (max param delta "
            f"{max_delta})")
