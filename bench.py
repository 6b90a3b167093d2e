"""Benchmark: the reference's headline Transformer training step
(reference: examples/cpp/Transformer/transformer.cc:172-210 — ELAPSED
TIME/THROUGHPUT printed around the epoch loop with execution fences).

One process, on a TPU: it exits non-zero before building anything when
``jax.default_backend()`` is not ``tpu``, raises on a ``device_kind`` the
machine model has no peak for, and lets every phase's exception end the
run — a number printed here was measured on the device it names.
Prints ONE JSON line on stdout (progress goes to stderr):
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": {...}}

``vs_baseline`` follows the OSDI'22 AE protocol (BASELINE.md): hybrid /
searched strategy throughput relative to pure data-parallel on the same
hardware; a single chip collapses both, so the ratio is 1.0 there.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _progress(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _build(batch_size, num_layers, seq, hidden, heads, mesh=None, tp_axis=None,
           compute_dtype=None):
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models.transformer import TransformerConfig, build_transformer

    cfg = TransformerConfig(hidden_size=hidden, num_heads=heads,
                            num_layers=num_layers, sequence_length=seq)
    ff = FFModel(FFConfig(batch_size=batch_size, seed=0,
                          compute_dtype=compute_dtype))
    build_transformer(ff, batch_size, cfg, tp_axis=tp_axis)
    ff.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
        metrics=[],
        mesh=mesh,
    )
    return ff, cfg


def _time_steps(ff, cfg, batch_size, warmup=3, iters=30):
    """Execution-fenced step timing (reference pattern:
    transformer.cc:172-210). The loss of iteration N depends on the params
    of iteration N-1, so fetching the final loss value fences the whole
    chain."""
    import jax

    cm = ff.compiled
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch_size, cfg.sequence_length, cfg.hidden_size)).astype(np.float32)
    y = rng.normal(size=(batch_size, cfg.sequence_length, 1)).astype(np.float32)
    xb = jax.device_put(x, cm.input_shardings[0])
    yb = jax.device_put(y, cm.label_sharding)
    key = jax.random.key(0)
    params, opt_state = cm.params, cm.opt_state
    for _ in range(warmup):
        params, opt_state, loss, _ = cm.train_step(params, opt_state, key, xb, yb)
    _ = float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_state, loss, _ = cm.train_step(params, opt_state, key, xb, yb)
    _ = float(loss)  # fences the full dependency chain
    t1 = time.perf_counter()
    cm.params, cm.opt_state = params, opt_state
    return (t1 - t0) / iters


def _measure() -> dict:
    import jax

    from flexflow_tpu.kernels import flash_attention as fa
    from flexflow_tpu.kernels import pallas_mode
    from flexflow_tpu.sim import detect_machine_model

    devs = jax.devices()
    n_dev = len(devs)
    platform, kind = devs[0].platform, devs[0].device_kind
    _progress(f"backend up: {platform} x{n_dev} ({kind})")
    # one table of peaks, the machine model's, keyed by device_kind:
    # a chip it does not know raises here, before anything is timed
    peak = detect_machine_model(n_dev).chip.peak_bf16_flops * n_dev

    # the reference benchmark config (transformer.cc:78-86): seq 512,
    # hidden 1024, 16 heads, 12 layers; batch 8 per the OSDI'22 bert.sh
    layers, seq, hidden, heads, per_dev_batch, iters = 12, 512, 1024, 16, 8, 30
    batch = per_dev_batch * n_dev

    # bf16 compute is the TPU-native headline (the MXU's matmul input type);
    # FLEXFLOW_BENCH_DTYPE=float32 forces full precision for comparison
    compute_dtype = os.environ.get("FLEXFLOW_BENCH_DTYPE", "bfloat16")
    if compute_dtype in ("float32", "fp32", "f32"):
        compute_dtype = None

    # the kernels against XLA's softmax(QK^T)V at the bench shape, forward
    # and backward in the compute dtype: a record only, the dispatch is a
    # rule over shapes (fa.engaged) and reads no measurement
    hd = hidden // heads
    _progress(f"timing flash attention at (seq={seq}, d={hd})...")
    tuned = fa.autotune(shape=(2, seq, heads, hd), causal=False,
                        dtype=compute_dtype or "float32", iters=5)
    flash_vs_xla = tuned["xla_ratio"]
    _progress(f"flash blocks={tuned['best']} vs XLA fused: "
              f"{flash_vs_xla}x "
              f"({'engaged' if fa.engaged(seq, seq, hd) else 'off'})")

    _progress(f"building model: layers={layers} seq={seq} hidden={hidden} "
              f"heads={heads} batch={batch} compute={compute_dtype or 'float32'}")
    t_build = time.perf_counter()
    ff, cfg = _build(batch, num_layers=layers, seq=seq, hidden=hidden,
                     heads=heads, compute_dtype=compute_dtype)
    _progress(f"model built in {time.perf_counter() - t_build:.1f}s; "
              f"timing ({iters} iters)...")
    # several timed windows: the MEDIAN is the headline and the spread is
    # recorded, so run-to-run drift is distinguishable from a real
    # dispatch-path regression
    n_windows = 3
    windows = [_time_steps(ff, cfg, batch, iters=iters)
               for _ in range(n_windows)]
    step_s = sorted(windows)[n_windows // 2]
    spread = (max(windows) - min(windows)) / step_s if step_s > 0 else 0.0
    throughput = batch / step_s
    _progress(f"step={step_s * 1e3:.2f} ms (median of {n_windows}, "
              f"spread {spread:.1%})  throughput={throughput:.2f} samples/s")

    fwd_flops = float(sum(op.flops() for op in ff.compiled.ops))
    mfu = 3.0 * fwd_flops / step_s / peak  # fwd+bwd ≈ 3x fwd FLOPs

    result = {
        "metric": "transformer_bert_train_throughput",
        "value": round(throughput, 2),
        "unit": "samples/s",
        "vs_baseline": 1.0,
        "detail": {
            "step_time_ms": round(step_s * 1e3, 2),
            "batch_size": batch,
            "devices": n_dev,
            "platform": platform,
            "device_kind": kind,
            "config": f"seq{seq}_hidden{hidden}_heads{heads}_layers{layers}",
            "fwd_flops_per_step": fwd_flops,
            "mfu": round(mfu, 4),
            "dtype": compute_dtype or "float32",
            "step_time_ms_windows": [round(w * 1e3, 2) for w in windows],
            "step_spread_rel": round(spread, 4),
            "flash_vs_xla": flash_vs_xla,
        },
    }

    # ---- fp32 comparison point (the reference's precision) ----------------
    if compute_dtype is not None:
        _progress("re-building in float32 for comparison...")
        ff32, _ = _build(batch, num_layers=layers, seq=seq, hidden=hidden,
                         heads=heads)
        step32 = _time_steps(ff32, cfg, batch, iters=iters)
        result["detail"]["step_time_ms_fp32"] = round(step32 * 1e3, 2)
        result["detail"]["bf16_speedup"] = round(step32 / step_s, 3)
        _progress(f"fp32 step={step32 * 1e3:.2f} ms "
                  f"(bf16 speedup {step32 / step_s:.2f}x)")
        del ff32

    # ---- Pallas kernels off: quantify the custom-kernel delta -------------
    # Only meaningful where the kernels actually engage (fa.engaged: a rule
    # over the shapes) — otherwise both builds are identical.
    pallas_active = (pallas_mode() == "compiled"
                     and ff.compiled.mesh.size == 1
                     and fa.engaged(seq, seq, hd))
    result["detail"]["pallas_active"] = pallas_active
    if pallas_active:
        _progress("re-building with Pallas kernels off...")
        os.environ["FLEXFLOW_TPU_PALLAS"] = "off"
        try:
            ff_off, _ = _build(batch, num_layers=layers, seq=seq,
                               hidden=hidden, heads=heads,
                               compute_dtype=compute_dtype)
            step_off = _time_steps(ff_off, cfg, batch, iters=iters)
        finally:
            os.environ.pop("FLEXFLOW_TPU_PALLAS", None)
        result["detail"]["step_time_ms_no_pallas"] = round(step_off * 1e3, 2)
        result["detail"]["pallas_speedup"] = round(step_off / step_s, 3)
        _progress(f"no-pallas step={step_off * 1e3:.2f} ms")

    # ---- vs_baseline: hybrid vs pure DP (OSDI'22 AE protocol) -------------
    if n_dev > 1:
        from flexflow_tpu import make_mesh

        _progress("timing pure data-parallel baseline...")
        ff_dp, _ = _build(batch, num_layers=layers, seq=seq, hidden=hidden,
                          heads=heads, mesh=make_mesh({"data": n_dev}),
                          compute_dtype=compute_dtype)
        step_dp = _time_steps(ff_dp, cfg, batch, iters=iters)
        result["vs_baseline"] = round(step_dp / step_s, 3)
        result["detail"]["dp_step_time_ms"] = round(step_dp * 1e3, 2)
    return result


def main() -> int:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"bench.py measures on a TPU; jax.default_backend() is "
              f"{backend!r} ({jax.devices()[0].device_kind} x"
              f"{len(jax.devices())}) — nothing was measured",
              file=sys.stderr)
        return 1
    result = _measure()
    # durable trend line: the record also accumulates in the run ledger
    # (.ffcache/obs/runs/) so tools/perf_sentinel.py can judge the next
    # run against this one
    from flexflow_tpu.obs.ledger import record_bench

    record_bench("bench", result,
                 perf={"metric": result["metric"], "value": result["value"],
                       "higher_is_better": True},
                 label=result["metric"])
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
