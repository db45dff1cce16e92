"""Zipf CTR rows: the paper's ad-click regime, without an (F,) weight map.

The same batches as the program's `repro.data.sparse_corpus.make_batch`
(bench/tests/test_traffic.py checks them bit for bit), made without the
dense (F,) map of true weights that it builds for every batch: the labels
look the sparse true weights up by id among their sorted ids instead. At
F = 2^27 the map alone is a 512 MiB write per batch, which would make the
generator, not the system, the thing measured.

    corpus = {"num_features": 1 << 27, "features_per_sample": 64,
              "min_features": 8, "zipf_alpha": 1.2, "signal_features": 4096,
              "positive_ratio": 0.75, "truth_seed": 0}
    b = make_batch(corpus, 4096, seed)  # {"ids", "vals", "labels"}
"""
from __future__ import annotations

import functools

import numpy as np

HASH = np.int64(2654435761)


def zipf_ids(rng: np.random.Generator, corpus: dict, n: int) -> np.ndarray:
    """Zipf ids in [0, F): the rank is hashed so that id and frequency
    rank are uncorrelated, as hashed feature strings are."""
    f = int(corpus["num_features"])
    raw = rng.zipf(corpus["zipf_alpha"], size=n).astype(np.int64)
    ranked = (raw - 1) % f
    return ((ranked * HASH) % np.int64(f)).astype(np.int32)


@functools.lru_cache(maxsize=4)
def _true_weights(num_features: int, signal_features: int, truth_seed: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(truth_seed + 7)
    ranks = np.arange(signal_features, dtype=np.int64)
    ids = ((ranks % num_features) * HASH
           % np.int64(num_features)).astype(np.int32)
    ids = np.unique(ids)
    w = rng.normal(0.0, 2.0, size=ids.shape[0]).astype(np.float32)
    return ids, w


def true_weights(corpus: dict) -> tuple[np.ndarray, np.ndarray]:
    """(sorted ids, weights) of the sparse ground truth: the Zipf head."""
    return _true_weights(int(corpus["num_features"]),
                         int(corpus["signal_features"]),
                         int(corpus["truth_seed"]))


def make_batch(corpus: dict, batch_size: int, seed: int) -> dict:
    """One padded-CSR batch: ids (B, K) int32 with -1 past each row's
    length, vals (B, K) float32, labels (B,) int32."""
    rng = np.random.default_rng(seed)
    k = int(corpus["features_per_sample"])
    ids = zipf_ids(rng, corpus, batch_size * k).reshape(batch_size, k)
    vals = np.ones((batch_size, k), np.float32)
    lens = rng.integers(int(corpus["min_features"]), k + 1, size=batch_size)
    mask = np.arange(k)[None, :] < lens[:, None]
    ids = np.where(mask, ids, -1).astype(np.int32)
    vals = np.where(mask, vals, 0.0).astype(np.float32)
    vals = vals / np.sqrt(np.maximum(lens, 1))[:, None].astype(np.float32)

    tid, tw = true_weights(corpus)
    look = np.clip(ids, 0, None)
    pos = np.clip(np.searchsorted(tid, look), 0, len(tid) - 1)
    w = np.where(tid[pos] == look, tw[pos], np.float32(0.0))
    logits = (w * vals * (ids >= 0)).sum(axis=1)
    bias = np.log(corpus["positive_ratio"] / (1 - corpus["positive_ratio"]))
    p = 1.0 / (1.0 + np.exp(-(logits + bias)))
    labels = (rng.random(batch_size) < p).astype(np.int32)
    return {"ids": ids, "vals": vals, "labels": labels}
