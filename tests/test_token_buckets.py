"""Token-native dynamic shapes (runtime/buckets.py + the bucketed
fit/eval paths in runtime/model.py, runtime/dataloader.py,
runtime/compiler.py).

The contracts that matter:

* the ladder/plan layer is a pure deterministic function of (permuted
  lengths, knobs): exact-boundary lengths land on their rung, the DYN
  codes fire at plan time instead of dispatch time, and rebuilding a
  plan is bit-stable;
* padded positions are provably inert: masked sparse-CE gives a padded
  position an exactly-zero loss term and an exactly-zero gradient row;
* a bucketed fit's loss trajectory and final params are BIT-IDENTICAL
  to the pad-to-max complement (same plan, width padded to the ladder
  top) — the padding the ladder removes never carried information;
* an unseen (rows, bucket) shape is a clean, counted, ledger-attributed
  compile miss (``fit_profile["buckets"]["new_compiles"]``), and
  replaying a seen plan compiles NOTHING new;
* the resolved ladder + token budget key the ledger cohort apart
  (the PR 12 cohort-fix pattern), and static-shape records stay
  untouched.
"""

import numpy as np
import pytest

from flexflow_tpu import (FFConfig, FFModel, LossType, MetricsType,
                          SGDOptimizer)
from flexflow_tpu.models import GPTConfig, build_gpt
from flexflow_tpu.runtime.buckets import (DynamicShapeError, PackingSpec,
                                          bucket_for, build_epoch_plan,
                                          plan_token_stats, resolve_ladder,
                                          row_lengths)

V = 32
S = 32


# ------------------------------------------------------------ pure planning
def test_resolve_ladder_pow2_and_explicit():
    assert resolve_ladder("pow2", 8, 48) == (8, 16, 32, 48)
    # the top rung is always the data's width — full rows must fit
    assert resolve_ladder("pow2", 8, 32) == (8, 16, 32)
    assert resolve_ladder("16,4,64", 1, 48) == (4, 16, 48)
    with pytest.raises(DynamicShapeError) as e:
        resolve_ladder("banana", 8, 32)
    assert e.value.code == "DYN003"
    with pytest.raises(DynamicShapeError):
        resolve_ladder("pow2", 8, 0)


def test_bucket_for_exact_boundaries():
    ladder = (8, 16, 32)
    # an exact-boundary length lands ON its rung, not the next one
    assert bucket_for(ladder, 8) == 8
    assert bucket_for(ladder, 9) == 16
    assert bucket_for(ladder, 16) == 16
    assert bucket_for(ladder, 32) == 32
    with pytest.raises(DynamicShapeError) as e:
        bucket_for(ladder, 33)
    assert e.value.code == "DYN001"


def test_row_lengths_trailing_contract():
    lab = np.full((3, 6), -1, np.int64)
    lab[0, :4] = 1
    lab[1, :6] = 2
    lab[2, :1] = 3
    assert row_lengths(lab).tolist() == [4, 6, 1]
    lab[0, 5] = 7  # interior padding: -1 before a valid token
    with pytest.raises(DynamicShapeError) as e:
        row_lengths(lab)
    assert e.value.code == "DYN002"


def test_plan_budget_packing_deterministic_and_bounded():
    rng = np.random.default_rng(3)
    lens = np.clip(rng.geometric(0.1, size=64), 2, 32)
    spec = PackingSpec(ladder=(8, 16, 32), token_budget=128,
                       batch_size=8)
    plan = build_epoch_plan(lens, spec)
    assert plan == build_epoch_plan(lens, spec)  # pure function
    assert sum(g.rows for g in plan) == 64       # budget mode covers all
    for g in plan:
        assert g.width in (8, 16, 32)
        assert g.pad_rows * g.width <= 128 or g.rows == 1
        assert g.pad_rows >= g.rows
        assert (g.pad_rows & (g.pad_rows - 1)) == 0  # pow2 rows
    valid, total = plan_token_stats(plan)
    assert valid == int(lens.sum()) and total >= valid
    with pytest.raises(DynamicShapeError) as e:
        build_epoch_plan(lens, PackingSpec(ladder=(8, 16, 32),
                                           token_budget=16, batch_size=8))
    assert e.value.code == "DYN004"


def test_plan_pad_max_shares_grouping_widens_dispatch():
    """The pad-to-max complement must keep the exact bucketed grouping
    (groups, rows, pad_rows) and differ ONLY in width — that is what
    makes its trajectories bit-comparable."""
    rng = np.random.default_rng(4)
    lens = np.clip(rng.geometric(0.12, size=48), 2, 32)
    kw = dict(ladder=(8, 16, 32), token_budget=128, batch_size=8)
    bucketed = build_epoch_plan(lens, PackingSpec(**kw))
    padmax = build_epoch_plan(lens, PackingSpec(pad_max=True, **kw))
    assert len(bucketed) == len(padmax)
    assert any(g.width < 32 for g in bucketed)
    for gb, gp in zip(bucketed, padmax):
        assert (gb.rows, gb.pad_rows, gb.valid_tokens) == \
            (gp.rows, gp.pad_rows, gp.valid_tokens)
        assert gp.width == 32
    vb, tb = plan_token_stats(bucketed)
    vp, tp = plan_token_stats(padmax)
    assert vb == vp and tb < tp  # strictly less padding


def test_plan_fixed_row_mode_keeps_loader_semantics():
    lens = np.asarray([3, 9, 2, 17, 5, 8, 30, 2, 4])  # 9 rows, batch 4
    spec = PackingSpec(ladder=(8, 16, 32), token_budget=0, batch_size=4)
    plan = build_epoch_plan(lens, spec)
    assert [g.rows for g in plan] == [4, 4]  # truncated to whole batches
    assert [g.width for g in plan] == [32, 32]
    lens2 = np.asarray([3, 5, 2, 7, 9, 16, 11, 12])
    plan2 = build_epoch_plan(lens2, spec)
    assert [g.width for g in plan2] == [8, 16]


# ------------------------------------------------------------ inert padding
def test_masked_loss_padded_rows_zero_grad():
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.runtime.loss import compute_loss

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(4, 8, 16)).astype(np.float32))
    labels = np.full((4, 8), -1, np.int32)
    labels[0, :5] = rng.integers(0, 16, 5)
    labels[1, :8] = rng.integers(0, 16, 8)
    # rows 2 and 3 are all padding (a quantized pad row)
    lab = jnp.asarray(labels)

    def loss(lg):
        return compute_loss(
            LossType.SPARSE_CATEGORICAL_CROSSENTROPY, lg, lab,
            from_logits=True, mask_padding=True)

    g = jax.grad(loss)(logits)
    assert float(loss(logits)) > 0
    assert np.all(np.asarray(g[2:]) == 0.0)           # inert rows
    assert np.all(np.asarray(g[0, 5:]) == 0.0)        # inert positions
    assert np.any(np.asarray(g[0, :5]) != 0.0)


# ------------------------------------------------------- bucketed fit paths
def _ragged(n, seed=0, min_len=2):
    rng = np.random.default_rng(seed)
    lengths = np.clip(rng.geometric(0.12, size=n), min_len, S)
    tokens = np.zeros((n, S), np.int32)
    labels = np.full((n, S), -1, np.int32)
    for i, ln in enumerate(lengths):
        tokens[i, :ln] = rng.integers(0, V, ln)
        labels[i, :ln] = rng.integers(0, V, ln)
    positions = np.tile(np.arange(S, dtype=np.int32), (n, 1))
    return [tokens, positions], labels


def _gpt(**cfg_kw):
    cfg_kw.setdefault("ledger", "off")
    ff = FFModel(FFConfig(batch_size=8, seed=0, **cfg_kw))
    build_gpt(ff, 8, S, GPTConfig(vocab_size=V, max_positions=S,
                                  hidden_size=32, num_heads=4,
                                  num_layers=2))
    ff.compile(optimizer=SGDOptimizer(lr=0.05),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY,
                        MetricsType.ACCURACY])
    return ff


def _params(ff):
    return {(o, w): np.asarray(v)
            for o, ws in ff.compiled.params.items()
            for w, v in ws.items()}


def test_bucketed_fit_bit_identical_to_pad_max():
    x, y = _ragged(48)
    kw = dict(seq_buckets="pow2", seq_bucket_min=8, token_budget=128)
    a = _gpt(**kw)
    b = _gpt(seq_bucket_pad_max="on", **kw)
    ha = a.fit(x, y, epochs=2, verbose=False)
    hb = b.fit(x, y, epochs=2, verbose=False)
    la = [pm.sparse_cce_loss for pm in ha]
    lb = [pm.sparse_cce_loss for pm in hb]
    # bit for bit where ONE set of programs runs twice: the bucketed fit
    # again, from the same seed-0 init over the same plan
    again = _gpt(**kw).fit(x, y, epochs=2, verbose=False)
    assert [pm.sparse_cce_loss for pm in again] == la
    # bucketed against pad-max is two sets of programs of different
    # widths. The padding is inert, but the loss and the gradients
    # contract over the position axis and XLA associates a sum by its
    # shape: epoch 1's losses, from the identical init, read
    # 1642.67431640625 and 1642.6741943359375, one float32 ULP (7.4e-8
    # of the value) apart, and ``==`` between them was never sound. 1e-6
    # is 8 ULPs, 4,000 times tighter than one bfloat16 rounding
    # (2**-8): a bf16 loss, accumulator or cast on either side fails it.
    assert abs(la[0] - lb[0]) <= 1e-6 * abs(lb[0])
    # params (and epoch 2, which runs on them) carry every step's
    # reassociated gradient sums
    assert np.allclose(la, lb, rtol=1e-4, atol=1e-6)
    pa, pb = _params(a), _params(b)
    assert set(pa) == set(pb)
    assert all(np.allclose(pa[k], pb[k], rtol=1e-4, atol=1e-6)
               for k in pa)
    # the bucketed side really dispatched multiple widths and measurably
    # less padding — the identity above is not vacuous
    assert a.fit_profile["buckets"]["known_shapes"] > 1
    assert (a.fit_profile["buckets"]["padded_token_fraction"]
            < b.fit_profile["buckets"]["padded_token_fraction"])


def test_unseen_bucket_is_counted_miss_replay_compiles_nothing():
    x, y = _ragged(48)
    ff = _gpt(seq_buckets="pow2", seq_bucket_min=8, token_budget=128)
    ff.fit(x, y, epochs=1, verbose=False)
    first = ff.fit_profile["buckets"]
    assert first["new_compiles"] > 0
    assert first["new_compiles"] == first["known_shapes"]
    # replay the identical plan: zero new (rows, bucket) shapes
    ff.fit(x, y, epochs=2, verbose=False)
    again = ff.fit_profile["buckets"]
    assert again["new_compiles"] == 0
    assert again["known_shapes"] == first["known_shapes"]
    assert again["ladder"] == first["ladder"]


def test_bucketed_eval_counts_misses_and_tokens():
    x, y = _ragged(48)
    ff = _gpt(seq_buckets="pow2", seq_bucket_min=8, token_budget=128)
    ff.fit(x, y, epochs=1, verbose=False)
    ff.eval(x, y, verbose=False)
    bk = ff.eval_profile["buckets"]
    # eval_step shapes are distinct from train_step shapes — they miss
    # once, then replay clean
    assert bk["new_compiles"] > 0
    assert 0 < bk["padded_token_fraction"] < 1
    ff.eval(x, y, verbose=False)
    assert ff.eval_profile["buckets"]["new_compiles"] == 0


def test_default_off_path_untouched():
    """seq_buckets=off must not change loader type, profile keys, or
    the strategy-cache signature — the historical programs trace
    unchanged."""
    from flexflow_tpu.search.cache import config_signature

    x, y = _ragged(16)
    ff = _gpt()
    ff.fit(x, y, epochs=1, verbose=False)
    assert "buckets" not in ff.fit_profile
    sig = config_signature(ff.config, {})
    assert "seq_buckets" not in sig and "token_budget" not in sig
    on = config_signature(
        FFConfig(seq_buckets="pow2", token_budget=128), {})
    assert on["seq_buckets"] == "pow2"


def test_dyn003_misconfigurations_fail_at_fit_entry():
    x, y = _ragged(16)
    with pytest.raises(DynamicShapeError):  # budget without a ladder
        _gpt(token_budget=128).fit(x, y, epochs=1, verbose=False)
    with pytest.raises(DynamicShapeError):  # bad pad_max spec
        _gpt(seq_buckets="pow2", seq_bucket_pad_max="banana").fit(
            x, y, epochs=1, verbose=False)


# ------------------------------------------------------------ ledger cohort
def test_resolved_ladder_and_budget_key_the_cohort():
    from flexflow_tpu.obs.ledger import cohort_key, model_context

    x, y = _ragged(16)
    off = _gpt()
    on = _gpt(seq_buckets="pow2", seq_bucket_min=8, token_budget=128)
    on.fit(x, y, epochs=1, verbose=False)
    ctx_off, ctx_on = model_context(off), model_context(on)
    # static-shape records stay knob-free: existing cohorts untouched
    assert "seq_bucket_ladder" not in ctx_off["knobs"]
    assert "token_budget" not in ctx_off["knobs"]
    # the bucketed record carries the RESOLVED envelope
    import json as _json

    assert _json.loads(ctx_on["knobs"]["seq_bucket_ladder"]) == \
        list(on._resolved_ladder)
    assert ctx_on["knobs"]["token_budget"] == 128
    ra = {"kind": "fit", "label": "m", "mesh": {},
          "knobs": ctx_off["knobs"], "machine": {"backend": "cpu"},
          "perf": {"metric": "fit.steps_per_s"}}
    rb = dict(ra, knobs=ctx_on["knobs"])
    assert cohort_key(ra) != cohort_key(rb)
