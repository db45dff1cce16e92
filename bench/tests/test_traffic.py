"""The benchmark's generators: the Zipf copy gives the program's batches
bit for bit; the Criteo-schema rows have the schema and dlrm's layout."""
import numpy as np
import pytest

from bench import common

ZIPF = {"num_features": 1 << 16, "features_per_sample": 64,
        "min_features": 8, "zipf_alpha": 1.2, "signal_features": 4096,
        "positive_ratio": 0.75, "truth_seed": 0}


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 5])
def test_zipf_ctr_is_make_batch_bit_for_bit(seed):
    from repro.data.sparse_corpus import CorpusSpec, make_batch

    gen = common.load_module("traffic", "zipf_ctr")
    spec = CorpusSpec(num_features=1 << 16, features_per_sample=64,
                      min_features=8, zipf_alpha=1.2,
                      signal_features=4096, positive_ratio=0.75, seed=0)
    want = make_batch(spec, 512, seed)
    got = gen.make_batch(ZIPF, 512, seed)
    for k in ("ids", "vals", "labels"):
        assert got[k].dtype == want[k].dtype
        np.testing.assert_array_equal(got[k], want[k])


def test_criteo_rows_have_the_schema_and_dlrm_layout():
    corpus = common.load_json("configs", "criteo-synth-dlrm.json")["corpus"]
    gen = common.load_module("traffic", "criteo_fields")
    card = gen.cardinalities(corpus)
    assert int(card.sum()) == corpus["num_features"]
    b = gen.make_batch(corpus, 4096, 2**31 + 3)
    assert b["ids"].shape == (4096, 39) and b["ids"].dtype == np.int32
    # every value lies in its own field's rows
    start = gen.offsets(corpus)
    assert (b["ids"] >= start).all() and (b["ids"] < start + card).all()
    np.testing.assert_allclose(b["vals"], 1 / np.sqrt(39), rtol=1e-6)
    assert len(np.unique(b["ids"][:, 13 + 8])) <= 3
    assert 0.15 < b["labels"].mean() < 0.45
    again = gen.make_batch(corpus, 4096, 2**31 + 3)
    for k in ("ids", "vals", "labels"):
        np.testing.assert_array_equal(b[k], again[k])


def test_criteo_value_index_is_a_permutation_of_rank():
    corpus = {"int_fields": 0, "int_buckets": 64,
              "cat_cardinalities": [1000, 3, 7]}
    gen = common.load_module("traffic", "criteo_fields")
    for n in gen.cardinalities(corpus):
        rank = np.arange(n, dtype=np.int64)
        assert len(np.unique(rank * gen._PERM % n)) == n
