"""Plain reference of sparse logistic regression, and its control.

Written from the model's definition, independent of the program: no
routing, no sharding, no hot/cold split and no import from `repro`. A
step over a batch gathers theta at each row's ids, takes the sigmoid of
sum(vals * theta), and forms per-slot gradients vals * (p - y) / B, which
it sums per id. Adagrad then updates each id: acc += g^2 and
theta -= lr * g / sqrt(acc + eps). Ids no batch has touched keep theta 0
and get g = 0, so only the ids of the batches are held.

`precision="float64"` is the reference. `precision="bfloat16"` is the
control: the same steps with every stored value and every operation's
result rounded to bfloat16, sums accumulated wider first as the chip's
matrix units do. It is the step below float32, the precision the
configurations state, and the comparison has to fail it.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

PRECISIONS = ("float64", "bfloat16")


def rounder(precision: str):
    if precision == "float64":
        return lambda x: np.asarray(x, np.float64)
    if precision == "bfloat16":
        return lambda x: np.asarray(x, np.float64).astype(
            ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"precision must be one of {PRECISIONS}: {precision!r}")


def hot_set(sample_ids: np.ndarray, threshold: float, max_hot: int
            ) -> np.ndarray:
    """The ids replicated as hot: of the ids whose share of the sample's
    slots is at least `threshold`, the `max_hot` most frequent (ties to the
    lower id), sorted."""
    ids = np.asarray(sample_ids).reshape(-1)
    uniq, counts = np.unique(ids[ids >= 0], return_counts=True)
    share = counts.astype(np.float32) / np.float32(max(int(counts.sum()), 1))
    keep = share >= np.float32(threshold)
    uniq, counts = uniq[keep], counts[keep]
    return np.sort(uniq[np.lexsort((uniq, -counts))[:max_hot]])


def train_steps(batches: list[dict], lr: float, eps: float,
                precision: str = "float64") -> dict:
    """Adagrad from a zero table over `batches` (dicts of ids (B, K) with
    -1 at empty slots, vals (B, K), labels (B,)).

    Returns ids (the distinct ids touched, sorted), grad1 (the first
    step's gradient at those ids), theta (the parameters after the last
    step at those ids) and losses (the mean NLL of each step)."""
    q = rounder(precision)
    ids_all = np.concatenate([np.asarray(b["ids"]).reshape(-1)
                              for b in batches])
    uniq = np.unique(ids_all[ids_all >= 0])
    theta = np.zeros(len(uniq))
    acc = np.zeros(len(uniq))
    losses, grad1 = [], None
    for b in batches:
        ids = np.asarray(b["ids"])
        valid = ids >= 0
        loc = np.searchsorted(uniq, np.where(valid, ids, uniq[0]))
        vals = q(np.where(valid, b["vals"], 0.0))
        y = np.asarray(b["labels"], np.float64)
        logits = q(q(vals * np.where(valid, theta[loc], 0.0)).sum(axis=1))
        p = q(1.0 / (1.0 + np.exp(-logits)))
        nll = q(np.where(y > 0, np.logaddexp(0.0, -logits),
                         np.logaddexp(0.0, logits)))
        losses.append(float(q(nll.mean())))
        g = q(q(vals * (p - y)[:, None]) / ids.shape[0])
        gsum = q(np.bincount(loc[valid], weights=g[valid],
                             minlength=len(uniq)))
        if grad1 is None:
            grad1 = gsum.copy()
        acc = q(acc + q(gsum * gsum))
        theta = q(theta - q(lr * q(gsum / q(np.sqrt(q(acc + eps))))))
    return {"ids": uniq, "grad1": grad1, "theta": theta, "losses": losses}
