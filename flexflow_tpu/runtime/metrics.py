"""Training metrics.

TPU-native equivalent of the reference's Metrics subsystem
(reference: include/flexflow/metrics_functions.h:44-79,
src/metrics_functions/ — PerfMetrics accumulated through a Legion future
chain; accuracy/cce/scce/MSE/RMSE/MAE). Here per-batch metrics are computed
inside the jitted step (a fused epilogue on the final op's output) and
accumulated host-side in :class:`PerfMetrics`; the future chain is replaced
by jax's async dispatch.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from ..ffconst import LossType, MetricsType
from .loss import label_positions, masked_row_sums, sparse_log_likelihood


@dataclasses.dataclass
class PerfMetrics:
    """Accumulated metrics (reference: metrics_functions.h PerfMetrics)."""

    train_all: int = 0
    train_correct: int = 0
    cce_loss: float = 0.0
    sparse_cce_loss: float = 0.0
    mse_loss: float = 0.0
    rmse_loss: float = 0.0
    mae_loss: float = 0.0

    def update(self, batch: Dict[str, float]) -> None:
        self.train_all += int(batch.get("count", 0))
        self.train_correct += int(batch.get("correct", 0))
        for k in ("cce_loss", "sparse_cce_loss", "mse_loss", "rmse_loss", "mae_loss"):
            if k in batch:
                setattr(self, k, getattr(self, k) + float(batch[k]))

    # -- device-side accumulation (fit/eval loops) ------------------------ #
    # Per-batch metrics stay on device across an epoch: accumulate() only
    # PARKS the per-step dicts (no host sync, not even an eager add on
    # the step loop's critical path — the reference chains PerfMetrics
    # through futures for the same reason, model.cc:2880); flush() folds
    # them in arrival order and converts once at the epoch boundary.
    # Parked entries are compacted into a running device accumulator
    # every _PENDING_CAP entries, so a million-step epoch holds a
    # bounded number of device scalars, never an unbounded list.
    _PENDING_CAP = 256

    def accumulate(self, batch: Dict) -> None:
        """Park one per-dispatch metric dict. The multi-step executable
        folds its k per-step dicts device-side in step order before
        returning (runtime/compiler.py train_k_steps), so every caller
        parks exactly one dict per dispatch."""
        pending = getattr(self, "_dev_pending", None)
        if pending is None:
            pending = self._dev_pending = []
        pending.append(batch)
        if len(pending) >= self._PENDING_CAP:
            self._compact()

    def _compact(self) -> None:
        """Fold parked entries (in arrival order) into the running
        device accumulator."""
        acc = getattr(self, "_dev_acc", None)
        for batch in getattr(self, "_dev_pending", None) or []:
            acc = self._fold(acc, batch)
        self._dev_acc = acc
        self._dev_pending = []

    def _fold(self, acc, batch: Dict):
        if acc is None:
            return dict(batch)
        # merge over the UNION of keys: a key present in only one side
        # (metrics sets can differ across steps, e.g. after a recompile)
        # must survive, not be silently dropped
        return {
            k: (acc[k] + batch[k]) if k in acc and k in batch
            else (acc[k] if k in acc else batch[k])
            for k in set(acc) | set(batch)
        }

    def flush(self) -> None:
        self._compact()
        acc = getattr(self, "_dev_acc", None)
        if acc:
            self.update({k: float(v) for k, v in acc.items()})
        self._dev_acc = None
        self._dev_pending = None

    @property
    def accuracy(self) -> float:
        return self.train_correct / max(1, self.train_all)

    def report(self, metrics: List[MetricsType]) -> str:
        parts = []
        if MetricsType.ACCURACY in metrics:
            parts.append(
                f"accuracy: {100.0 * self.accuracy:.2f}% "
                f"({self.train_correct} / {self.train_all})"
            )
        if MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY in metrics:
            parts.append(f"sparse_cce: {self.sparse_cce_loss / max(1, self.train_all):.4f}")
        if MetricsType.CATEGORICAL_CROSSENTROPY in metrics:
            parts.append(f"cce: {self.cce_loss / max(1, self.train_all):.4f}")
        if MetricsType.MEAN_SQUARED_ERROR in metrics:
            parts.append(f"mse: {self.mse_loss / max(1, self.train_all):.4f}")
        if MetricsType.ROOT_MEAN_SQUARED_ERROR in metrics:
            parts.append(f"rmse: {self.rmse_loss / max(1, self.train_all):.4f}")
        if MetricsType.MEAN_ABSOLUTE_ERROR in metrics:
            parts.append(f"mae: {self.mae_loss / max(1, self.train_all):.4f}")
        return "  ".join(parts)


def compute_batch_metrics(
    metrics: List[MetricsType],
    loss_type: LossType,
    logits: jnp.ndarray,
    labels: jnp.ndarray,
    from_logits: bool = False,
    mask_padding: bool = False,
    log_likelihood: Optional[jnp.ndarray] = None,
) -> Dict[str, jnp.ndarray]:
    """Per-batch metric computation (reference: Metrics::compute kernels,
    src/metrics_functions/metrics_functions.cu). Runs inside jit.
    ``from_logits`` mirrors compute_loss: True when the graph does not end
    in a softmax. ``mask_padding`` mirrors compute_loss's masked
    token-level path: ``-1``-labelled positions drop out of count /
    correct / cce sums exactly, with the same row-major two-stage
    reduction so bucket widths fold bit-identically.
    ``log_likelihood``: the per-position terms of a sparse loss on raw
    logits where the caller's loss already made them
    (``loss.sparse_ce_from_logits``); without them they are made here by
    the same function."""
    sparse = loss_type is LossType.SPARSE_CATEGORICAL_CROSSENTROPY
    want_scce = (sparse
                 and MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY in metrics)
    lab = label_positions(logits, labels) if sparse else None
    ll = None
    if want_scce:
        if from_logits:
            ll = (log_likelihood if log_likelihood is not None
                  else sparse_log_likelihood(logits, lab))
        else:
            ll = jnp.take_along_axis(
                jnp.log(jnp.clip(logits, 1e-10, 1.0)),
                (jnp.maximum(lab, 0) if mask_padding else lab)[..., None],
                axis=-1)[..., 0]
    if sparse and logits.ndim >= 3 and mask_padding:
        valid = lab >= 0
        out: Dict[str, jnp.ndarray] = {"count": jnp.sum(valid)}
        if MetricsType.ACCURACY in metrics:
            pred = jnp.argmax(logits, axis=-1)
            out["correct"] = jnp.sum(
                jnp.sum(valid & (pred == lab), axis=-1))
        if want_scce:
            out["sparse_cce_loss"] = -jnp.sum(masked_row_sums(ll, valid))
        return out
    # token-level metrics (seq2seq/NMT): positions count as the batch,
    # matching compute_loss's rank-3 path (runtime/loss.py)
    out: Dict[str, jnp.ndarray] = {
        "count": jnp.asarray(lab.size if sparse else logits.shape[0])}

    def _logp():
        if from_logits:
            return jax.nn.log_softmax(logits, axis=-1)
        return jnp.log(jnp.clip(logits, 1e-10, 1.0))

    if MetricsType.ACCURACY in metrics:
        pred = jnp.argmax(logits, axis=-1)
        true = lab.astype(pred.dtype) if sparse \
            else jnp.argmax(labels, axis=-1)
        out["correct"] = jnp.sum(pred == true)
    if want_scce:
        out["sparse_cce_loss"] = -jnp.sum(ll)
    if MetricsType.CATEGORICAL_CROSSENTROPY in metrics and not sparse:
        out["cce_loss"] = -jnp.sum(labels * _logp())
    if MetricsType.MEAN_SQUARED_ERROR in metrics:
        out["mse_loss"] = jnp.sum((logits - labels) ** 2)
    if MetricsType.ROOT_MEAN_SQUARED_ERROR in metrics:
        # per-sample RMSE summed over the batch (reference:
        # metrics_functions.cu RMSE accumulation)
        out["rmse_loss"] = jnp.sum(
            jnp.sqrt(jnp.mean((logits - labels) ** 2, axis=-1))
        )
    if MetricsType.MEAN_ABSOLUTE_ERROR in metrics:
        out["mae_loss"] = jnp.sum(jnp.abs(logits - labels))
    return out
