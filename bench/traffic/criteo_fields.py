"""Rows of the Criteo Kaggle schema: 39 fields, one value each, with
synthetic values.

The schema of the Criteo Display Advertising Challenge (Kaggle 2014): 13
integer fields and 26 categorical ones, every row carrying one value of
each, so K = 39 with no padding. The table is laid out as
facebookresearch/dlrm lays out its Kaggle embedding tables: one row per
(field, value), the fields' tables one after another, so F is the sum of
the fields' cardinalities and no two values share a row. The corpus group
of the configuration gives the categorical cardinalities (dlrm's counts
over the challenge's training rows, a missing value counted as one of
them) and the buckets of each log-bucketized integer field.

What is not from the source: a row draws, per field, a Zipf-ranked value
(`zipf_alpha`, wrapped into the field's cardinality), and a fixed
permutation per field turns the frequency rank into the value's index, so
that index and frequency are uncorrelated, as dlrm's sorted vocabularies
are. Labels follow a sparse true-weight model like the Zipf corpus's: the
`signal_values` most frequent values of each field carry a weight drawn
from the truth seed, and a bias sets the positive rate.

    b = make_batch(corpus, 4096, seed)  # {"ids", "vals", "labels"}
"""
from __future__ import annotations

import functools

import numpy as np

# a prime above every cardinality, so that rank * _PERM mod n is a
# permutation of [0, n)
_PERM = np.int64(2654435761)


def cardinalities(corpus: dict) -> np.ndarray:
    """Values per field: integer fields first, then the categorical ones."""
    ints = [int(corpus["int_buckets"])] * int(corpus["int_fields"])
    return np.asarray(ints + list(corpus["cat_cardinalities"]), np.int64)


def offsets(corpus: dict) -> np.ndarray:
    """The first row of each field's table."""
    card = cardinalities(corpus)
    return np.concatenate([[0], np.cumsum(card)[:-1]]).astype(np.int64)


@functools.lru_cache(maxsize=4)
def _truth(fields: int, signal_values: int, truth_seed: int) -> np.ndarray:
    rng = np.random.default_rng(truth_seed + 7)
    return rng.normal(0.0, 2.0, size=(fields, signal_values)) \
        .astype(np.float32)


def make_batch(corpus: dict, batch_size: int, seed: int) -> dict:
    """One batch: ids (B, 39) int32, vals (B, 39) float32 all 1/sqrt(39),
    labels (B,) int32."""
    card = cardinalities(corpus)
    k = len(card)
    if k != int(corpus["features_per_sample"]):
        raise ValueError(f"{k} fields but features_per_sample = "
                         f"{corpus['features_per_sample']}")
    if int(card.sum()) != int(corpus["num_features"]):
        raise ValueError(f"the fields hold {int(card.sum())} values but "
                         f"num_features = {corpus['num_features']}")
    rng = np.random.default_rng(seed)
    raw = rng.zipf(corpus["zipf_alpha"], size=(batch_size, k))
    rank = (raw.astype(np.int64) - 1) % card[None, :]
    ids = (offsets(corpus)[None, :]
           + rank * _PERM % card[None, :]).astype(np.int32)
    vals = np.full((batch_size, k), 1.0 / np.sqrt(k), np.float32)

    s = int(corpus["signal_values"])
    truth = _truth(k, s, int(corpus["truth_seed"]))
    field = np.broadcast_to(np.arange(k), rank.shape)
    w = np.where(rank < s, truth[field, np.minimum(rank, s - 1)],
                 np.float32(0.0))
    logits = (w * vals).sum(axis=1)
    bias = np.log(corpus["positive_ratio"] / (1 - corpus["positive_ratio"]))
    p = 1.0 / (1.0 + np.exp(-(logits + bias)))
    labels = (rng.random(batch_size) < p).astype(np.int32)
    return {"ids": ids, "vals": vals, "labels": labels}
