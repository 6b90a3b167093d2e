"""Loss functions.

TPU-native equivalent of the reference's Loss subsystem
(reference: include/flexflow/loss_functions.h:27-86, src/loss_functions/ —
sparse/categorical cross-entropy, MSE, identity; backward kernels scale by
``1/global_batch_size``). Here the loss is a scalar jax function inside the
jitted step; its gradient (the reference's hand-written backward kernels)
comes from ``jax.grad``. The ``scale_factor = 1/global_batch`` semantics are
preserved by taking the *mean* over the global batch.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..ffconst import LossType


def label_positions(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Sparse labels as int32 in the shape of ``logits`` less its last
    axis: one label per position for token-level logits (B, ..., V), the
    first label column for (B, V)."""
    if logits.ndim >= 3:
        return labels.reshape(logits.shape[:-1]).astype(jnp.int32)
    return labels.reshape(labels.shape[0], -1)[:, 0].astype(jnp.int32)


def _label_hit(x, labels):
    # the one-hot of the labels as a comparison against an iota: it
    # fuses into whatever reads it, where a gather or a scatter cannot
    return jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1) \
        == labels[..., None]


def _log_likelihood(logits, labels):
    x = logits.astype(jnp.float32)
    m = jnp.max(x, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(x - m[..., None]), axis=-1))
    picked = jnp.sum(jnp.where(_label_hit(x, labels), x, 0.0), axis=-1)
    return picked - lse, lse


@jax.custom_vjp
def sparse_log_likelihood(logits: jnp.ndarray,
                          labels: jnp.ndarray) -> jnp.ndarray:
    """log softmax(logits)[label] per position, float32, from raw logits
    (..., V) in whatever float dtype the head wrote and int32 labels
    (...) — in ONE pass over the logits, forward and backward.

    ``log_softmax`` followed by ``take_along_axis`` makes XLA write the
    (positions, V) float32 log-probabilities to HBM (a gather cannot
    fuse with its producer) and its transpose scatter and re-reduce
    them; at a vocabulary of 50k that is most of what the loss costs.
    Here the label's logit is a masked sum inside the pass that sums the
    exponentials, the arithmetic is float32 on the fly (``float32(x)``
    of a bfloat16 ``x`` is exact), and the only (positions, V) array
    kept for the backward is the logits as given. A label that names no
    class (``-1`` padding) takes no logit: its value is ``-lse`` and is
    the caller's to mask.
    """
    return _log_likelihood(logits, labels)[0]


def _sparse_ll_fwd(logits, labels):
    ll, lse = _log_likelihood(logits, labels)
    return ll, (logits, labels, lse)


def _sparse_ll_bwd(res, ct):
    logits, labels, lse = res
    x = logits.astype(jnp.float32)
    # one elementwise expression: it fuses into whatever reads it (the
    # head's two backward products), no scatter and no second reduction
    g = ct[..., None] * (_label_hit(x, labels).astype(jnp.float32)
                         - jnp.exp(x - lse[..., None]))
    return g.astype(logits.dtype), None


sparse_log_likelihood.defvjp(_sparse_ll_fwd, _sparse_ll_bwd)


def masked_row_sums(ll: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Per-row sums of the per-position terms with EXACT zeros at the
    padded positions. Rows first, across rows after: pow2 bucket widths
    nest a narrower row's pairwise reduction tree inside a wider one's
    (the extra leaves are exact zeros), so the same batch padded to two
    different rungs folds bit-identically."""
    return jnp.sum(jnp.where(valid, ll, 0.0), axis=-1)


def sparse_ce_from_logits(logits: jnp.ndarray, labels: jnp.ndarray,
                          mask_padding: bool = False):
    """Sparse cross-entropy on raw logits: ``(loss, log-likelihoods)``,
    the scalar mean and the per-position float32 terms it is the mean
    of (:func:`sparse_log_likelihood`), which the batch metrics read
    too instead of making them again."""
    _count_path("one_pass")
    lab = label_positions(logits, labels)
    ll = sparse_log_likelihood(logits, lab)
    if mask_padding and logits.ndim >= 3:
        valid = lab >= 0
        n = jnp.maximum(1, jnp.sum(valid)).astype(ll.dtype)
        return -jnp.sum(masked_row_sums(ll, valid)) / n, ll
    return -jnp.mean(ll), ll


def _count_path(form: str) -> None:
    # which form a cross-entropy took, counted once per trace beside
    # the attention op's path counter: the rule is over the loss type
    # and the graph's last op, and a chip run has to be able to say
    # which one it timed
    from ..obs.metrics import metrics_registry

    metrics_registry().counter(f"loss.path.{form}").inc()


def compute_loss(
    loss_type: LossType, logits: jnp.ndarray, labels: jnp.ndarray,
    from_logits: bool = False, mask_padding: bool = False,
) -> jnp.ndarray:
    """Return scalar loss (mean over batch).

    ``logits`` is the final op's output. For the cross-entropy losses the
    final op is conventionally a Softmax (as in the reference, where
    Loss::backward peels the softmax — loss_functions.cc); the compiler
    passes ``from_logits=True`` when the graph does NOT end in a softmax,
    in which case a fused log-softmax is applied here instead — raw logits
    through the probability path would be clipped into [1e-10, 1] and the
    gradient destroyed. Sparse labels on raw logits take
    :func:`sparse_ce_from_logits` (the logits in any float dtype, one
    pass); every other form expects float32.

    ``mask_padding`` (token-level sparse CE only; set by the compiler
    when ``config.seq_buckets`` is active): positions labelled ``-1``
    contribute an EXACTLY-zero loss term — so their cotangents, and
    every weight-gradient contribution flowing from them, are exact
    float zeros — and the mean divides by the valid-token count. The
    reduction is :func:`masked_row_sums`, so bucket widths fold
    bit-identically.
    """
    if loss_type is LossType.SPARSE_CATEGORICAL_CROSSENTROPY:
        if from_logits:
            return sparse_ce_from_logits(logits, labels, mask_padding)[0]
        _count_path("probabilities")
        logp = jnp.log(jnp.clip(logits, 1e-10, 1.0))
        if mask_padding and logits.ndim >= 3:
            lab = label_positions(logits, labels)
            valid = lab >= 0
            ll = jnp.take_along_axis(
                logp, jnp.where(valid, lab, 0)[..., None], axis=-1)[..., 0]
            n = jnp.maximum(1, jnp.sum(valid)).astype(ll.dtype)
            return -jnp.sum(masked_row_sums(ll, valid)) / n
        if logits.ndim >= 3:
            # token-level CE (seq2seq / NMT): logits (B, ..., V) with one
            # label per position — flatten positions into the batch
            logp = logp.reshape(-1, logp.shape[-1])
        ll = jnp.take_along_axis(
            logp, label_positions(logits, labels).reshape(-1, 1), axis=-1)
        return -jnp.mean(ll)
    if loss_type is LossType.CATEGORICAL_CROSSENTROPY:
        _count_path("log_softmax" if from_logits else "probabilities")
        logp = (jax.nn.log_softmax(logits, axis=-1) if from_logits
                else jnp.log(jnp.clip(logits, 1e-10, 1.0)))
        return -jnp.mean(jnp.sum(labels * logp, axis=-1))
    if loss_type is LossType.MEAN_SQUARED_ERROR_AVG_REDUCE:
        # mean over batch*features (reference: loss_functions.cc AVG_REDUCE
        # scale_factor = 2/volume)
        return jnp.mean((logits - labels) ** 2)
    if loss_type is LossType.MEAN_SQUARED_ERROR_SUM_REDUCE:
        # sum over features, mean over batch (reference: scale 1/batch)
        return jnp.mean(jnp.sum((logits - labels) ** 2, axis=-1))
    if loss_type is LossType.IDENTITY:
        return jnp.mean(logits)
    raise ValueError(loss_type)


def loss_from_string(s: str) -> LossType:
    """reference: flexflow_cffi.py loss-type string mapping."""
    m = {
        "categorical_crossentropy": LossType.CATEGORICAL_CROSSENTROPY,
        "sparse_categorical_crossentropy": LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        "mean_squared_error": LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
        "mse": LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
        "identity": LossType.IDENTITY,
    }
    return m[s]
