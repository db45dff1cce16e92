"""`core/hot_sharding.py` unit tests + serving hot-cache correctness.

The hot-sharding primitives (select_hot / split_hot /
load_imbalance) were consumer-less until the serving subsystem; this file
pins their semantics directly, then asserts the serving-facing contract of
`repro.serve.hot_cache`: a cached hit is BIT-IDENTICAL to the uncached
sparse predict while the mirror is fresh, and the staleness bound forces a
refresh (never serving stale parameter values after training moved on).
"""
import collections

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import DPMREngine, hot_ids_from_corpus
from repro.configs.base import DPMRConfig
from repro.core import hot_sharding
from repro.data import get_source
from repro.launch.mesh import make_host_mesh
from repro.serve import HotCacheConfig, HotFeatureCache, ServeMetrics

INT_MAX = hot_sharding.INT_MAX
F = 1 << 10


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


def test_select_hot_counts_occurrences():
    ids = np.asarray([[0, 1, 1], [2, -1, 1]], np.int32)
    # 1 occurs three times: the single slot goes to it
    assert hot_sharding.select_hot(ids, 0.0, 1).tolist() == [1]
    assert hot_sharding.select_hot(ids, 0.0, 4).tolist() == [0, 1, 2,
                                                             INT_MAX]


def test_select_hot_drops_padding_only():
    ids = np.asarray([-1, -1, 3], np.int32)
    # padding neither counts as a feature nor dilutes the frequencies
    assert hot_sharding.select_hot(ids, 1.0, 2).tolist() == [3, INT_MAX]


def test_select_hot_any_shape():
    flat = np.asarray([0, 0, 1, 2, 2, 2], np.int32)
    assert np.array_equal(hot_sharding.select_hot(flat, 0.0, 2),
                          hot_sharding.select_hot(flat.reshape(2, 3), 0.0, 2))


def _with_counts(counts):
    """Flat ids in which feature i occurs counts[i] times."""
    return np.repeat(np.arange(len(counts), dtype=np.int32), counts)


def test_select_hot_threshold_and_sorting():
    ids = _with_counts([10, 0, 5, 1])                  # total 16
    got = hot_sharding.select_hot(ids, 0.3, 3)
    # freq >= 0.3 keeps features 0 (0.625) and 2 (0.3125) only
    assert got.tolist() == [0, 2, INT_MAX]


def test_select_hot_max_hot_cap():
    got = hot_sharding.select_hot(_with_counts([4, 3, 2, 1]), 0.0, 2)
    assert got.tolist() == [0, 1]        # two largest counts, sorted


def test_select_hot_zero_count_never_selected():
    got = hot_sharding.select_hot(_with_counts([0, 2, 0, 0]), 0.0, 4)
    assert got.tolist() == [1, INT_MAX, INT_MAX, INT_MAX]


def test_select_hot_nothing_eligible():
    got = hot_sharding.select_hot(_with_counts([1, 1]), 0.9, 2)
    assert got.tolist() == [INT_MAX, INT_MAX]


@pytest.mark.parametrize("threshold,max_hot", [(0.0, 8), (0.01, 64),
                                                (0.05, 4), (1.0, 8)])
def test_select_hot_matches_brute_force(threshold, max_hot):
    """The selection == a plain count over every feature: frequency >=
    threshold, the max_hot most frequent, ties to the lower id."""
    rng = np.random.default_rng(int(threshold * 100) + max_hot)
    ids = np.minimum(rng.zipf(1.3, size=(64, 16)), F) - 1
    ids = np.where(rng.random(ids.shape) < 0.1, -1, ids).astype(np.int32)
    counts = collections.Counter(ids[ids >= 0].tolist())
    total = sum(counts.values())
    eligible = sorted((-c, i) for i, c in counts.items()
                      if np.float32(c) / np.float32(total)
                      >= np.float32(threshold))
    want = sorted(i for _, i in eligible[:max_hot])
    want += [INT_MAX] * (max_hot - len(want))
    got = hot_sharding.select_hot(ids, threshold, max_hot)
    assert got.tolist() == want


def test_split_hot_partition():
    hot_ids = jnp.asarray([2, 5] + [INT_MAX] * 2, jnp.int32)
    flat = jnp.asarray([2, 3, 5, -1], jnp.int32)
    slot, is_hot, cold = (np.asarray(a) for a in
                          hot_sharding.split_hot(flat, hot_ids))
    assert is_hot.tolist() == [True, False, True, False]
    assert slot.tolist() == [0, -1, 1, -1]
    assert cold.tolist() == [-1, 3, -1, -1]


def test_split_hot_roundtrips_every_id():
    # every input id is either hot (slot >= 0) or cold (cold >= 0) or
    # padding — never two of the three
    hot_ids = jnp.asarray([1, 4, 7, INT_MAX], jnp.int32)
    flat = jnp.asarray([0, 1, 2, 4, 6, 7, -1, 9], jnp.int32)
    slot, is_hot, cold = (np.asarray(a) for a in
                          hot_sharding.split_hot(flat, hot_ids))
    for i, f in enumerate(np.asarray(flat)):
        if f < 0:
            assert not is_hot[i] and cold[i] == -1
        elif is_hot[i]:
            assert cold[i] == -1 and np.asarray(hot_ids)[slot[i]] == f
        else:
            assert cold[i] == f and slot[i] == -1


F_BIG = 1 << 27


def _split_oracle(ids, hot_ids):
    """`split_hot` in numpy: searchsorted (side left), then the match."""
    pos = np.clip(np.searchsorted(hot_ids, ids, side="left"), 0,
                  len(hot_ids) - 1)
    is_hot = (hot_ids[pos] == ids) & (ids >= 0)
    return (np.where(is_hot, pos, -1).astype(np.int32), is_hot,
            np.where(is_hot | (ids < 0), -1, ids).astype(np.int32))


@pytest.mark.parametrize("kind", ["mixed", "empty", "all_hot"])
@pytest.mark.parametrize("h", [1, 8, 512, 4096])
def test_split_hot_matches_searchsorted_oracle(h, kind):
    """The compare-and-reduce gives the search's outputs bit for bit, at
    hot sets of 1 to 4096: padding -1, id 0 and the largest id below F, a
    partly padded hot set with a repeated id, an empty (all INT_MAX) one,
    and a batch whose every id is hot."""
    rng = np.random.default_rng(h)
    real = h - h // 4 if kind != "empty" else 0
    hot = {0, F_BIG - 1} if real >= 2 else ({0} if real else set())
    while len(hot) < real:
        hot.add(int(rng.integers(1, F_BIG - 1)))
    hot_ids = np.full((h,), int(INT_MAX), np.int32)
    hot_ids[:real] = np.sort(np.fromiter(hot, np.int64, real))
    if kind == "mixed" and real >= 4:       # a repeated id: its first slot
        hot_ids[real // 2] = hot_ids[real // 2 - 1]
    n = 4096
    if kind == "all_hot":
        ids = hot_ids[rng.integers(0, real, n)]
    else:
        ids = rng.integers(0, F_BIG, n)
        pick = rng.random(n)
        if real:
            ids = np.where(pick < 0.3, hot_ids[rng.integers(0, real, n)],
                           ids)
        ids = np.where(pick > 0.9, -1, ids)
        ids[:3] = [-1, 0, F_BIG - 1]
    ids = ids.astype(np.int32)
    got = [np.asarray(a) for a in
           hot_sharding.split_hot(jnp.asarray(ids), jnp.asarray(hot_ids))]
    for g, w in zip(got, _split_oracle(ids, hot_ids)):
        assert g.dtype == w.dtype and np.array_equal(g, w)
    assert got[1].all() == (kind == "all_hot")


def test_load_imbalance_uniform_vs_skewed():
    # 4 shards x block 2: one id per owner -> perfectly balanced
    even = jnp.asarray([0, 2, 4, 6], jnp.int32)
    assert float(hot_sharding.load_imbalance(even, 4, 2)) == 1.0
    # all ids on owner 0 -> max/mean = num_shards
    skew = jnp.asarray([0, 1, 0, 1], jnp.int32)
    assert float(hot_sharding.load_imbalance(skew, 4, 2)) == 4.0


def test_load_imbalance_ignores_padding():
    ids = jnp.asarray([0, 2, 4, 6, -1, -1], jnp.int32)
    assert float(hot_sharding.load_imbalance(ids, 4, 2)) == 1.0


# ---------------------------------------------------------------------------
# serving hot cache
# ---------------------------------------------------------------------------


def _trained_engine(max_hot=16, steps=8):
    mesh = make_host_mesh(1, 1)
    cfg = DPMRConfig(num_features=F, max_features_per_sample=8,
                     max_hot=max_hot, hot_threshold=0.001)
    src = get_source("zipf_sparse", batch_size=8, num_batches=8,
                     num_features=F, features_per_sample=8, seed=3)
    # a real model-hot set, so the cache mirror must gather from BOTH the
    # replicated hot table and the sharded cold table
    hot = hot_ids_from_corpus(cfg, src.iter_batches(limit=4), mesh)
    eng = DPMREngine(cfg, mesh, hot_ids=hot)
    eng.fit_sgd(src.iter_batches(), steps=steps)
    return eng, src


def _request(src, i):
    b = src.batch(i)
    return b["ids"], b["vals"]


def test_cached_hit_bit_identical_to_sparse_path():
    eng, src = _trained_engine()
    cache = HotFeatureCache(eng, HotCacheConfig(max_hot=64, threshold=0.0,
                                                window=64,
                                                refresh_every=1000),
                            ServeMetrics())
    ids, vals = _request(src, 0)
    # make every feature of the request window-hot (threshold 0 selects
    # anything observed; 64 slots cover the <=64 distinct ids)
    cache.observe(ids)
    got = cache.lookup(ids, vals)
    assert got is not None, "fully-observed request must hit"
    ref = eng.predict({"ids": ids, "vals": vals})
    np.testing.assert_array_equal(got, ref)   # bit-exact, not approx
    assert cache.metrics.snapshot()["cache_hits"] == 1


def test_unseen_feature_misses():
    eng, src = _trained_engine()
    cache = HotFeatureCache(eng, HotCacheConfig(max_hot=64, threshold=0.0,
                                                window=64,
                                                refresh_every=1000),
                            ServeMetrics())
    ids, vals = _request(src, 0)
    cache.observe(ids)
    cache.lookup(ids, vals)                   # builds the mirror
    other = np.full_like(ids, -1)
    other[0, 0] = (int(ids.max()) + 1) % F    # a feature never observed
    assert cache.lookup(other, vals) is None
    assert cache.metrics.snapshot()["cache_misses"] == 1


def test_staleness_bound_forces_refresh():
    eng, src = _trained_engine()
    cache = HotFeatureCache(eng, HotCacheConfig(max_hot=64, threshold=0.0,
                                                window=64, refresh_every=3),
                            ServeMetrics())
    ids, vals = _request(src, 0)
    cache.observe(ids)
    for _ in range(7):
        assert cache.lookup(ids, vals) is not None
    m = cache.metrics.snapshot()
    # 7 lookups at refresh_every=3: initial gather + 2 staleness refreshes
    assert m["cache_refreshes"] == 3
    assert m["cache_stale_refreshes"] == 2
    assert cache.staleness == 1               # one lookup since the last


def test_step_change_refreshes_and_tracks_new_params():
    eng, src = _trained_engine()
    cache = HotFeatureCache(eng, HotCacheConfig(max_hot=64, threshold=0.0,
                                                window=64,
                                                refresh_every=1000),
                            ServeMetrics())
    ids, vals = _request(src, 0)
    cache.observe(ids)
    before = cache.lookup(ids, vals)
    assert before is not None
    # training moves the resident parameters; the mirror must notice the
    # step change and re-gather BEFORE answering, not serve stale values
    eng.fit_sgd(src.iter_batches(), steps=4)
    after = cache.lookup(ids, vals)
    assert after is not None
    m = cache.metrics.snapshot()
    assert m["cache_step_refreshes"] == 1
    assert not np.array_equal(before, after), "params moved; so must probs"
    np.testing.assert_array_equal(after,
                                  eng.predict({"ids": ids, "vals": vals}))


def test_window_eviction_drops_old_features():
    eng, src = _trained_engine()
    cache = HotFeatureCache(eng, HotCacheConfig(max_hot=64, threshold=0.0,
                                                window=2, refresh_every=1),
                            ServeMetrics())
    ids0, vals0 = _request(src, 0)
    ids1, vals1 = _request(src, 1)
    cache.observe(ids0)
    assert cache.lookup(ids0, vals0) is not None
    # push two newer requests through a window of 2: ids0 falls out
    cache.observe(ids1)
    cache.observe(ids1)
    only0 = set(np.unique(ids0[ids0 >= 0])) - set(np.unique(ids1[ids1 >= 0]))
    if only0:    # zipf heads may overlap entirely; only assert when not
        assert cache.lookup(ids0, vals0) is None


def test_empty_window_never_hits():
    eng, src = _trained_engine()
    cache = HotFeatureCache(eng, HotCacheConfig(max_hot=8, threshold=0.0,
                                                window=4, refresh_every=10),
                            ServeMetrics())
    ids, vals = _request(src, 0)
    assert cache.lookup(ids, vals) is None    # nothing observed yet
