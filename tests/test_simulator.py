"""Simulator / cost-model unit tests with the deterministic 'test' chip.

The reference has NO simulator unit tests (SURVEY.md §4 "what's missing");
these lock the analytic formulas so search regressions are catchable.
"""

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.runtime.compiler import build_ops
from flexflow_tpu.core.parallel_tensor import ParallelDim, ParallelTensorShape
from flexflow_tpu.ffconst import DataType
from flexflow_tpu.sim import (
    CHIP_PRESETS,
    OpCostModel,
    SimpleMachineModel,
    Simulator,
)


def _mlp_ops(axis_sizes, strategies=None):
    ff = FFModel(FFConfig(batch_size=32))
    x = ff.create_tensor((32, 64), DataType.FLOAT, name="x")
    h = ff.dense(x, 128, name="fc1")
    y = ff.dense(h, 16, name="fc2")
    input_ps = {
        x.tensor_id: ParallelTensorShape(
            (ParallelDim(32, axis_sizes.get("data", 1), "data" if axis_sizes.get("data", 1) > 1 else None)
             if axis_sizes.get("data", 1) > 1 else ParallelDim(32),
             ParallelDim(64)),
            DataType.FLOAT,
        )
    }
    ops, _ = build_ops(ff.layers, input_ps, axis_sizes, strategies or {})
    return ops


def test_collective_formulas():
    m = SimpleMachineModel(CHIP_PRESETS["test"], 4)
    # ring all-gather of 1 MB per device over 4: 3 * (1MB / 2e10 + 1us)
    b = 1e6
    assert np.isclose(m.allgather_time(b, 4), 3 * (b / 2e10 + 1e-6))
    # all-reduce = 2 * (n-1) shard transfers
    assert np.isclose(m.allreduce_time(b, 4), 2 * 3 * (b / 4 / 2e10 + 1e-6))
    assert m.allreduce_time(b, 1) == 0.0
    assert m.permute_time(b, 4) == b / 1e10 + 1e-6


def test_op_cost_roofline():
    ops = _mlp_ops({"data": 1})
    cm = OpCostModel(SimpleMachineModel(CHIP_PRESETS["test"], 1))
    fc1 = next(o for o in ops if o.name == "fc1")
    c = cm.measure(fc1)
    # flops = 2*32*64*128; compute = flops/1e12; bytes/(1e11) dominates?
    flops = 2 * 32 * 64 * 128
    byts = (32 * 64 + 32 * 128 + 64 * 128 + 128) * 4
    want = max(flops / 1e12, byts / 1e11)
    assert np.isclose(c.forward_time, want)
    assert np.isclose(c.backward_time, 2 * want)
    assert c.sync_time == 0.0  # no data axis => no grad sync
    # memoization: same object back
    assert cm.measure(fc1) is c


def test_dp_adds_grad_sync_and_divides_compute():
    axis = {"data": 4}
    ops = _mlp_ops(axis)
    machine = SimpleMachineModel(CHIP_PRESETS["test"], 4)
    cm = OpCostModel(machine)
    fc1 = next(o for o in ops if o.name == "fc1")
    c = cm.measure(fc1)  # axis sizes stamped on ops by build_ops
    # batch split 4 ways: per-device flops / 4
    flops = 2 * 32 * 64 * 128 / 4
    byts = (32 * 64 / 4 + 32 * 128 / 4 + 64 * 128 + 128) * 4
    assert np.isclose(c.forward_time, max(flops / 1e12, byts / 1e11))
    # weights replicated over data axis -> allreduce sync > 0
    assert c.sync_time > 0.0
    kernel_bytes = 64 * 128 * 4
    bias_bytes = 128 * 4
    want_sync = machine.allreduce_time(kernel_bytes, 4) + machine.allreduce_time(bias_bytes, 4)
    assert np.isclose(c.sync_time, want_sync)


def test_tp_linear_charges_contraction_allreduce():
    axis = {"data": 1, "model": 4}
    strategies = {"fc2": {"in": "model"}}
    ff = FFModel(FFConfig(batch_size=32))
    x = ff.create_tensor((32, 64), DataType.FLOAT, name="x")
    h = ff.dense(x, 128, name="fc1", )
    # shard fc1 out-features, fc2 contracts over them
    strategies["fc1"] = {"out": "model"}
    y = ff.dense(h, 16, name="fc2")
    input_ps = {x.tensor_id: ParallelTensorShape.unpartitioned((32, 64))}
    ops, _ = build_ops(ff.layers, input_ps, axis, strategies)
    machine = SimpleMachineModel(CHIP_PRESETS["test"], 4)
    sim = Simulator(machine)
    fc2 = next(o for o in ops if o.name == "fc2")
    # fc2's kernel in-dim is sharded on model but output is not -> allreduce
    t = sim._comm_time(fc2, backward=False)
    assert t > 0.0


def test_simulate_runtime_prefers_dp_at_large_batch():
    """Sanity: with a large batch and small weights, pure DP beats pure TP
    (same property the reference search exploits)."""
    machine = SimpleMachineModel(CHIP_PRESETS["test"], 4)

    B = 4096  # large enough that TP's batch-scaling activation all-reduce
    #           outweighs DP's fixed-size weight sync

    def step_time(axis_sizes, strategies):
        ff = FFModel(FFConfig(batch_size=B))
        x = ff.create_tensor((B, 64), DataType.FLOAT, name="x")
        h = ff.dense(x, 64, name="fc1")
        y = ff.dense(h, 8, name="fc2")
        if axis_sizes.get("data", 1) > 1:
            ips = ParallelTensorShape(
                (ParallelDim(B, 4, "data"), ParallelDim(64)), DataType.FLOAT
            )
        else:
            ips = ParallelTensorShape.unpartitioned((B, 64))
        ops, _ = build_ops(ff.layers, {x.tensor_id: ips}, axis_sizes, strategies)
        return Simulator(machine).simulate_runtime(ops)

    t_dp = step_time({"data": 4}, {})
    t_tp = step_time({"model": 4}, {"fc1": {"out": "model"}, "fc2": {"in": "model"}})
    assert t_dp < t_tp


def test_task_graph_and_memory():
    ops = _mlp_ops({"data": 1})
    sim = Simulator(SimpleMachineModel(CHIP_PRESETS["test"], 1))
    tasks = sim.build_task_graph(ops)
    kinds = [t.kind for t in tasks]
    assert kinds.count("fwd") == len(ops)
    assert kinds.count("bwd") == len(ops)
    assert "update" in kinds
    mu = sim.memory_usage(ops)
    w = (64 * 128 + 128 + 128 * 16 + 16) * 4
    assert mu.weights == w
    assert mu.optimizer_state == 2 * w
    assert sim.fits_memory(ops)


def test_sp_attention_comm_priced_and_modes_differ():
    """The simulator charges sequence-parallel attention's schedule comm
    (ring permutes vs Ulysses all-to-alls) — previously the generic rules
    saw none, making the seq_mode candidates indistinguishable."""
    from flexflow_tpu import ActiMode
    from flexflow_tpu.sim.simulator import Simulator as _Sim

    machine = SimpleMachineModel(CHIP_PRESETS["test"], 8)
    sim = Simulator(machine, OpCostModel(machine))
    axis = {"data": 2, "seq": 4}

    def attn_ops(seq_mode):
        ff = FFModel(FFConfig(batch_size=8))
        x = ff.create_tensor((8, 64, 32), DataType.FLOAT, name="x")
        ff.multihead_attention(x, x, x, 32, 4, name="attn",
                               strategy={"seq": "seq", "seq_mode": seq_mode})
        input_ps = {x.tensor_id: ParallelTensorShape(
            (ParallelDim(8, 2, "data"), ParallelDim(64), ParallelDim(32)),
            DataType.FLOAT)}
        ops, _ = build_ops(ff.layers, input_ps, axis,
                           {"attn": {"seq": "seq", "seq_mode": seq_mode}})
        return next(o for o in ops if o.name == "attn")

    ring = sim._comm_time(attn_ops("ring"), backward=False)
    a2a = sim._comm_time(attn_ops("a2a"), backward=False)
    assert ring > 0 and a2a > 0
    assert ring != a2a  # distinguishable to the search


def test_zero_optimizer_shrinks_search_memory_model():
    """--zero-optimizer: full_search charges 1/dp of the optimizer state
    per device (runtime: ZeRO-1 shards it over the data axis)."""
    from flexflow_tpu.search.unity import full_search

    ff = FFModel(FFConfig(batch_size=64))
    x = ff.create_tensor((64, 256), DataType.FLOAT, name="x")
    t = ff.dense(x, 512)
    ff.softmax(t)
    machine = SimpleMachineModel(CHIP_PRESETS["test"], 8)

    r_repl = full_search(ff.layers, [x], machine,
                         FFConfig(batch_size=64),
                         mesh_shapes=[{"data": 8}])
    r_zero = full_search(ff.layers, [x], machine,
                         FFConfig(batch_size=64, zero_optimizer=True),
                         mesh_shapes=[{"data": 8}])
    assert r_zero.est_memory < r_repl.est_memory


def _branchy_ops(axis_sizes, strategies=None, k=2, width=256):
    """x -> k parallel TP-sharded dense branches -> concat -> head."""
    ff = FFModel(FFConfig(batch_size=32))
    x = ff.create_tensor((32, 64), DataType.FLOAT, name="x")
    outs = [ff.dense(x, width, name=f"b{i}") for i in range(k)]
    cat = ff.concat(outs, axis=-1, name="cat")
    ff.dense(cat, 16, name="head")
    input_ps = {
        x.tensor_id: ParallelTensorShape(
            (ParallelDim(32), ParallelDim(64)), DataType.FLOAT)
    }
    ops, _ = build_ops(ff.layers, input_ps, axis_sizes, strategies or {})
    return ops


def test_backward_is_a_dag_not_a_chain():
    """Reverse dependency structure (reference: simulator.cc:850-905 —
    bwd tasks depend on their consumers' bwd, not a global chain): the two
    branches' bwd tasks must have the SAME dep (the concat's bwd), and the
    first op's bwd must not depend on the last op's bwd."""
    sim = Simulator(SimpleMachineModel(CHIP_PRESETS["test"], 4))
    ops = _branchy_ops({"data": 1})
    tasks = sim.build_task_graph(ops)
    by_name = {t.name: i for i, t in enumerate(tasks)}
    cat_bwd = by_name["cat:bwd"]
    b0_deps = tasks[by_name["b0:bwd"]].deps
    b1_deps = tasks[by_name["b1:bwd"]].deps
    assert b0_deps == (cat_bwd,) and b1_deps == (cat_bwd,)
    # grad sync waits on EVERY branch's backward
    gs = tasks[by_name["grad_sync"]]
    assert by_name["b0:bwd"] in gs.deps and by_name["b1:bwd"] in gs.deps


def test_branch_comm_overlaps_compute_in_backward():
    """Two independent TP branches: each bwd emits a collective on the
    network lane, which overlaps the sibling's bwd compute — makespan <
    serialized sum (the VERDICT round-2 done-criterion; the chain model
    charged everything serially)."""
    sim = Simulator(SimpleMachineModel(CHIP_PRESETS["test"], 4),
                    overlap_grad_sync=False)
    strategies = {"b0": {"in": "model"}, "b1": {"in": "model"},
                  "_axis_sizes": None}
    strategies = {k: v for k, v in strategies.items() if v is not None}
    ops = _branchy_ops({"model": 4}, strategies, width=512)
    tasks = sim.build_task_graph(ops)
    # the sharded-contraction branches must actually emit fwd collectives
    comm = [t for t in tasks if t.kind == "comm" and t.run_time > 0]
    assert len(comm) >= 2
    makespan = sim.simulate_runtime(ops) - sim.machine.chip.step_overhead
    serial = sum(t.run_time for t in tasks)
    assert makespan < serial * 0.999


def test_straight_chain_unchanged_by_dag_backward():
    """A straight chain has no branch overlap: DAG deps must reproduce the
    chain schedule (fwd+bwd+sync accumulate serially)."""
    sim = Simulator(SimpleMachineModel(CHIP_PRESETS["test"], 1),
                    overlap_grad_sync=False)
    ops = _mlp_ops({"data": 1})
    tasks = sim.build_task_graph(ops)
    total = sim.simulate_runtime(ops) - sim.machine.chip.step_overhead
    assert np.isclose(total, sum(t.run_time for t in tasks))


def test_pipe_boundary_bytes_use_real_cut_tensors():
    """_pipe_adjusted charges the ACTUAL stage-cut tensor, not the mean
    output (VERDICT weak item 4). The FLOP balancer puts the boundary
    right after the dominant 'wide' layer, whose (8, 4096) activation is
    the real cut — 2x what the old mean-output heuristic would charge."""
    from flexflow_tpu.search.unity import _stage_cut_bytes

    ff = FFModel(FFConfig(batch_size=8))
    x = ff.create_tensor((8, 1024), name="x")
    h = ff.dense(x, 4096, name="wide")   # dominant FLOPs -> stage cut here
    h = ff.dense(h, 8, name="narrow")
    h = ff.dense(h, 4096, name="wide2")
    h = ff.dense(h, 8, name="out")
    cut = _stage_cut_bytes(ff.layers, 2)
    assert cut == 4.0 * 8 * 4096  # exactly the crossing tensor's bytes
    sizes = [4.0 * np.prod(t.dims) for l in ff.layers for t in l.outputs]
    mean_heuristic = sum(sizes) / len(sizes)  # what the old model charged
    assert not np.isclose(cut, mean_heuristic)
    # a skip connection crossing the same boundary is charged too
    ff2 = FFModel(FFConfig(batch_size=8))
    x2 = ff2.create_tensor((8, 1024), name="x")
    a = ff2.dense(x2, 4096, name="wide")
    b = ff2.dense(a, 8, name="narrow")
    c = ff2.dense(b, 4096, name="wide2")
    ff2.add(a, c, name="skip")  # 'a' crosses the cut twice, counted once
    cut2 = _stage_cut_bytes(ff2.layers, 2)
    assert cut2 >= cut  # wide's activation + narrow's output cross


def test_per_op_family_backward_factors():
    """Backward/forward ratios are per-family (reference: per-op
    measure_operator_cost, e.g. linear.cc:792 — the uniform 2x misranked
    strategies with different fwd/bwd asymmetry)."""
    from flexflow_tpu.ffconst import OpType

    ff = FFModel(FFConfig(batch_size=16))
    x = ff.create_tensor((16, 64), DataType.FLOAT, name="x")
    ids = ff.create_tensor((16, 8), DataType.INT32, name="ids")
    e = ff.embedding(ids, 50000, 64, name="emb")   # huge table
    h = ff.dense(x, 128, name="fc")
    h = ff.relu(h, name="act")
    h = ff.layer_norm(h, axes=[1], name="ln")
    input_ps = {
        t.tensor_id: ParallelTensorShape(
            tuple(ParallelDim(s) for s in t.dims), t.dtype)
        for t in (x, ids)
    }
    ops, _ = build_ops(ff.layers, input_ps, {"data": 1}, {})
    cm = OpCostModel(SimpleMachineModel(CHIP_PRESETS["test"], 1))
    by = {o.name: cm.measure(o) for o in ops}
    byop = {o.name: o for o in ops}
    # pinned family ratios
    assert np.isclose(by["fc"].backward_time, 2.0 * by["fc"].forward_time)
    assert np.isclose(by["ln"].backward_time, 1.5 * by["ln"].forward_time)
    # weightless elementwise: one pass (the old model charged 2x)
    assert np.isclose(by["act"].backward_time, by["act"].forward_time)
    # embedding backward is bytes-bound on the TOUCHED rows, not a factor
    # of the table-sized forward: far below 2x fwd for a huge vocab
    emb = by["emb"]
    assert emb.backward_time < 0.25 * emb.forward_time
    assert cm.bwd_factor(byop["fc"]) == 2.0
    # attention family factor
    from flexflow_tpu.sim.cost_model import BWD_FACTORS
    assert BWD_FACTORS[OpType.MULTIHEAD_ATTENTION] == 2.5
    assert BWD_FACTORS[OpType.CONV2D] == 2.0


def test_detect_machine_model_keys_on_reported_device_kind(monkeypatch):
    """The v5e reports device_kind "TPU v5 lite": that string, not a
    fall-through, selects the v5e preset; a kind with no preset raises;
    and no environment variable moves the preset's step_overhead."""
    import types

    import jax

    from flexflow_tpu.sim import detect_machine_model
    from flexflow_tpu.sim.machine_model import CHIP_PRESETS

    def devices_of(kind, n=4):
        return lambda *a, **k: [types.SimpleNamespace(
            platform="tpu", device_kind=kind)] * n

    monkeypatch.setattr(jax, "devices", devices_of("TPU v5 lite"))
    m = detect_machine_model()
    assert m.chip == CHIP_PRESETS["v5e"] and m.num_devices() == 4
    for platforms in ("", "tpu", "cpu", "proxy", "proxy,cpu"):
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
        assert (detect_machine_model(1).chip.step_overhead
                == CHIP_PRESETS["v5e"].step_overhead)
    monkeypatch.setattr(jax, "devices", devices_of("TPU v5"))
    assert detect_machine_model().chip == CHIP_PRESETS["v5p"]
    monkeypatch.setattr(jax, "devices", devices_of("TPU v9 mega"))
    with pytest.raises(ValueError, match="TPU v9 mega"):
        detect_machine_model()
