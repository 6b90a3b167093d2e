"""Sparse cross-entropy on raw logits in one pass (``runtime/loss.py``
``sparse_log_likelihood``): value and gradient against
``-take_along_axis(log_softmax(float32 logits))``, the metric that reads
the same per-position terms, and the counter that says which form a
step's loss took."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import (ActiMode, DataType, FFConfig, FFModel, LossType,
                          MetricsType, SGDOptimizer)
from flexflow_tpu.models.gpt import GPTConfig, build_gpt
from flexflow_tpu.obs.metrics import metrics_registry
from flexflow_tpu.runtime.loss import (compute_loss, sparse_ce_from_logits,
                                       sparse_log_likelihood)
from flexflow_tpu.runtime.metrics import compute_batch_metrics

SCCE = LossType.SPARSE_CATEGORICAL_CROSSENTROPY
V = 1031  # 50,257 cut down: no multiple of 128, nor of 8
FORMS = ("one_pass", "log_softmax", "probabilities")


def _case(rank, dtype, masked, seed=0):
    rng = np.random.default_rng(seed)
    lead = (6,) if rank == 2 else (3, 8)
    logits = jnp.asarray(4.0 * rng.normal(size=lead + (V,)), jnp.float32)
    labels = rng.integers(0, V, size=lead).astype(np.int32)
    labels.flat[0], labels.flat[1] = 0, V - 1  # the vocabulary's two ends
    if masked and rank == 3:
        labels[1, :] = -1   # a whole padded row
        labels[2, 5:] = -1  # and a padded tail
    return logits.astype(dtype), jnp.asarray(labels)


def _reference(logits, labels, masked):
    """Today's other path: log_softmax of the float32 logits, a gather,
    the masked mean over the valid positions."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    valid = labels >= 0 if masked else jnp.ones(labels.shape, bool)
    ll = jnp.take_along_axis(
        logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(valid, ll, 0.0)) / jnp.maximum(1, valid.sum())


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rank", [2, 3])
def test_value_and_gradient_against_log_softmax(rank, dtype, masked):
    logits, labels = _case(rank, dtype, masked)

    def loss(lg):
        return compute_loss(SCCE, lg, labels, from_logits=True,
                            mask_padding=masked)

    got, g = jax.value_and_grad(loss)(logits)
    want, g_ref = jax.value_and_grad(
        lambda lg: _reference(lg, labels, masked))(logits.astype(jnp.float32))
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    # the cotangent comes back in the dtype the head wrote its logits in
    assert g.dtype == logits.dtype and g.shape == logits.shape
    g, g_ref = np.asarray(g, np.float32), np.asarray(g_ref)
    if dtype == jnp.float32:
        np.testing.assert_allclose(g, g_ref, rtol=1e-5, atol=1e-9)
    else:  # the float32 cotangent, rounded once to bfloat16
        np.testing.assert_allclose(g, g_ref, rtol=2.0 ** -8, atol=1e-9)
    if masked and rank == 3:
        # padded positions: exact zeros, in the loss's terms and in what
        # flows back from them
        assert np.all(g[1] == 0.0) and np.all(g[2, 5:] == 0.0)
        assert np.any(g[2, :5] != 0.0)
        _, ll = sparse_ce_from_logits(logits, labels, mask_padding=True)
        rows = np.asarray(jnp.sum(jnp.where(labels >= 0, ll, 0.0), axis=-1))
        assert rows[1] == 0.0


def test_log_likelihood_is_float32_arithmetic_on_the_logits_as_given():
    """bfloat16 logits are read as they are (``float32(x)`` is exact), so
    the terms equal those of their float32 copy to the last bit; labels
    at 0 and V - 1 pick the first and the last logit."""
    logits, labels = _case(3, jnp.bfloat16, False, seed=1)
    ll = sparse_log_likelihood(logits, labels)
    assert ll.dtype == jnp.float32 and ll.shape == labels.shape
    np.testing.assert_array_equal(
        np.asarray(ll),
        np.asarray(sparse_log_likelihood(logits.astype(jnp.float32), labels)))
    x = np.asarray(logits, np.float64)
    lse = np.log(np.exp(x - x.max(-1, keepdims=True)).sum(-1)) + x.max(-1)
    np.testing.assert_allclose(np.asarray(ll)[0, 0], x[0, 0, 0] - lse[0, 0],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ll)[0, 1],
                               x[0, 1, V - 1] - lse[0, 1], rtol=1e-6)


@pytest.mark.parametrize("rank", [2, 3])
def test_metric_is_count_times_loss_on_an_unmasked_batch(rank):
    logits, labels = _case(rank, jnp.bfloat16, False, seed=2)
    loss, ll = sparse_ce_from_logits(logits, labels)
    wanted = [MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY,
              MetricsType.ACCURACY]
    given = compute_batch_metrics(wanted, SCCE, logits, labels, True, False,
                                  ll)
    made = compute_batch_metrics(wanted, SCCE, logits, labels, True)
    assert int(given["count"]) == labels.size
    np.testing.assert_allclose(float(given["sparse_cce_loss"]),
                               labels.size * float(loss), rtol=1e-6)
    # without the loss's terms the metric makes them by the same function
    assert float(made["sparse_cce_loss"]) == float(given["sparse_cce_loss"])
    pred = np.argmax(np.asarray(logits, np.float32), axis=-1)
    assert int(given["correct"]) == int((pred == np.asarray(labels)).sum())


def _mlp(ff, softmax):
    x = ff.create_tensor((8, 16), DataType.FLOAT, name="x")
    t = ff.dense(x, 16, ActiMode.RELU, name="fc")
    t = ff.dense(t, 4, name="head")
    if softmax:
        ff.softmax(t, name="sm")


def _gpt(ff, softmax):
    del softmax
    build_gpt(ff, 8, 16, GPTConfig(vocab_size=61, max_positions=16,
                                   hidden_size=32, num_heads=2,
                                   num_layers=1))


@pytest.mark.parametrize("build,softmax,loss_type,compute_dtype,form", [
    (_gpt, False, SCCE, "bfloat16", "one_pass"),  # the fit cell's kind
    (_mlp, False, SCCE, None, "one_pass"),
    (_mlp, True, SCCE, None, "probabilities"),
    (_mlp, False, LossType.CATEGORICAL_CROSSENTROPY, None, "log_softmax"),
    (_mlp, True, LossType.CATEGORICAL_CROSSENTROPY, None, "probabilities"),
], ids=["gpt-bf16", "mlp-logits", "mlp-softmax", "dense-labels",
        "dense-labels-softmax"])
def test_loss_path_counter(build, softmax, loss_type, compute_dtype, form):
    """``loss.path.<form>`` says which form the traced steps' loss took:
    decided by the loss type and the graph's last op alone."""
    reg = metrics_registry()
    before = {f: reg.counter(f"loss.path.{f}").value for f in FORMS}
    ff = FFModel(FFConfig(batch_size=8, seed=0, search_cache="off",
                          ledger="off", compute_dtype=compute_dtype))
    build(ff, softmax)
    ff.compile(optimizer=SGDOptimizer(lr=0.05), loss_type=loss_type,
               metrics=[MetricsType.ACCURACY])
    cm = ff.compiled
    assert cm.from_logits is (not softmax)
    jax.eval_shape(lambda *a: cm.train_step(*a), cm.params, cm.opt_state,
                   jax.random.key(0), *_example_batch(cm))
    taken = {f for f in FORMS
             if reg.counter(f"loss.path.{f}").value > before[f]}
    assert taken == {form}


def _example_batch(cm):
    """Zeros for the step's inputs and labels: one label a position for
    token-level logits, else as the label tensor says."""
    arrays = [jnp.zeros(tuple(t.dims), t.dtype.to_jnp())
              for t in cm.input_tensors]
    lab, logits = cm.label_tensor, cm.logits_tensor
    dims = (tuple(logits.dims[:-1]) if len(logits.dims) >= 3
            else tuple(lab.dims))
    return arrays + [jnp.zeros(dims, lab.dtype.to_jnp())]


def test_train_step_jaxpr_has_no_gather_scatter_or_log_softmax():
    """The one-pass step's jaxpr, forward and backward: nothing of
    (batch, sequence, vocabulary) goes through a gather or a scatter,
    which are what made XLA write the float32 log-probabilities out
    (``tests/test_tpu_lowering.py`` reads the compiled program)."""
    ff = FFModel(FFConfig(batch_size=8, seed=0, search_cache="off",
                          ledger="off", compute_dtype="bfloat16"))
    _gpt(ff, False)
    ff.compile(optimizer=SGDOptimizer(lr=0.05), loss_type=SCCE,
               metrics=[MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY])
    cm = ff.compiled
    text = str(jax.make_jaxpr(lambda *a: cm.train_step(*a))(
        cm.params, cm.opt_state, jax.random.key(0), *_example_batch(cm)))
    vocab_lines = [ln for ln in text.splitlines() if "8,16,61]" in ln]
    assert vocab_lines
    assert not any(" gather[" in ln or "scatter" in ln for ln in vocab_lines)
    assert "log_softmax" not in text
