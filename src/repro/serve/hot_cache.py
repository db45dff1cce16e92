"""Host-side hot-feature parameter cache — the Zipf-head fast path.

The paper's §4 observation cuts both ways at serving time: under Zipf
traffic a handful of head features appears in almost every request. Those
features' parameters fit trivially on the serving host, so a request built
ENTIRELY of cached head features can be answered from a locally mirrored
dense slice — no micro-batch, no compiled step, no sparse exchange. Only
requests touching the Zipf tail go through the coalesced `predict_padded`
path.

This module is the serving consumer of `repro.core.hot_sharding`:

  select_hot       picks the head set from a sliding window of recent
                   request ids (frequency >= `threshold`, capped at
                   `max_hot`) exactly like the trainer's initParameters-time
                   statistic
  split_hot        classifies the selected ids against the MODEL's
                   replicated hot set, so the mirror gathers each value from
                   the right table (`state.hot` for model-hot features,
                   `state.cold` for owner-sharded ones)

Staleness contract (documented in docs/SERVING.md):

  - a hit is answered from the mirror only while the mirror is FRESH:
    at most `refresh_every` lookups old AND gathered at the engine's
    current `state.step`;
  - crossing either bound does not serve stale values — the next lookup
    refreshes the mirror first (counted in `cache_stale_refreshes` /
    `cache_step_refreshes`), then answers;
  - within freshness, a cached hit is bit-identical to the uncached sparse
    path: the mirror holds exact f32 parameter values and the hit compute
    runs the same `sum(vals * theta, axis=-1) -> sigmoid` as the device
    predict stage (tests/test_hot_sharding.py asserts equality).
"""
from __future__ import annotations

import collections
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dpmr, hot_sharding
from repro.serve.metrics import ServeMetrics


@dataclasses.dataclass(frozen=True)
class HotCacheConfig:
    """Hot-cache knobs.

    max_hot:        mirror slots (select_hot cap) — the head-set size
    threshold:      minimum in-window frequency for a feature to be cached
    window:         sliding request window feeding select_hot
    refresh_every:  staleness bound, in lookups: a mirror older than this
                    many served requests is refreshed before the next hit
    """

    max_hot: int = 256
    threshold: float = 0.001
    window: int = 512
    refresh_every: int = 256

    def __post_init__(self):
        if self.max_hot < 1:
            raise ValueError(f"max_hot must be >= 1: {self.max_hot}")
        if self.window < 1:
            raise ValueError(f"window must be >= 1: {self.window}")
        if self.refresh_every < 1:
            raise ValueError(
                f"refresh_every must be >= 1: {self.refresh_every}")


# the device predict stage's own head (core.dpmr.predict_probs) on
# mirrored parameters, so a fresh hit is bit-identical to the sparse path
_hit_predict = jax.jit(dpmr.predict_probs)


class HotFeatureCache:
    """Sliding-window hot-set mirror over a live `DPMREngine` state.

    Thread-safe: `observe`/`lookup` take an internal lock, so client
    threads and the flusher can share one cache. The mirror gathers values
    lazily (first lookup) and again whenever stale (see the module
    docstring's staleness contract).
    """

    def __init__(self, engine, config: HotCacheConfig | None = None,
                 metrics: ServeMetrics | None = None):
        self.engine = engine
        self.config = config or HotCacheConfig()
        self.metrics = metrics or ServeMetrics()
        self._lock = threading.Lock()
        self._window: collections.deque = collections.deque(
            maxlen=self.config.window)          # flat id arrays, one/request
        self._ids: np.ndarray | None = None     # sorted, INT_MAX padded
        self._vals: np.ndarray | None = None    # f32, aligned with _ids
        self._mirror_step = -1                  # engine step at last gather
        self._lookups_since_refresh = 0

    # -- observation & freshness --------------------------------------------

    def observe(self, ids: np.ndarray) -> None:
        """Feed one request's ids into the sliding frequency window."""
        with self._lock:
            self._window.append(np.asarray(ids, np.int32).reshape(-1))

    @property
    def staleness(self) -> int:
        """Lookups served since the mirror was last gathered."""
        with self._lock:
            return self._lookups_since_refresh

    @property
    def hot_ids(self) -> np.ndarray:
        """The currently mirrored feature ids (unpadded, sorted)."""
        with self._lock:
            if self._ids is None:
                return np.empty((0,), np.int32)
            return self._ids[self._ids != hot_sharding.INT_MAX].copy()

    def _fresh(self) -> bool:
        return (self._ids is not None
                and self._lookups_since_refresh < self.config.refresh_every
                and self._mirror_step == int(self.engine.state.step))

    # -- mirror refresh -----------------------------------------------------

    def refresh(self) -> None:
        """Re-derive the hot set from the window and re-gather its values."""
        with self._lock:
            self._refresh_locked()

    def _refresh_locked(self) -> None:
        state = self.engine.state
        if self._window:
            flat = np.concatenate(list(self._window))
        else:
            flat = np.empty((0,), np.int32)
        sel = hot_sharding.select_hot(flat, self.config.threshold,
                                      self.config.max_hot)
        valid = sel != hot_sharding.INT_MAX
        safe = jnp.asarray(np.where(valid, sel, 0))
        # model-hot features live in the replicated `hot` table, everything
        # else in the owner-sharded `cold` table — exactly the split the
        # device forward makes, so mirrored values are the exact f32
        # parameters a sparse predict would fetch
        hot_slot, is_hot, _ = hot_sharding.split_hot(safe, state.hot_ids)
        vals = jnp.where(is_hot, state.hot[jnp.clip(hot_slot, 0)],
                         state.cold[safe])
        vals = jnp.where(valid, vals, 0.0)
        self._ids = sel
        self._vals = np.asarray(jax.device_get(vals), np.float32)
        self._mirror_step = int(state.step)
        self._lookups_since_refresh = 0
        self.metrics.count("cache_refreshes")

    # -- the fast path ------------------------------------------------------

    def lookup(self, ids: np.ndarray,
               vals: np.ndarray) -> np.ndarray | None:
        """Answer a request from the mirror, or None (miss -> sparse path).

        A request hits iff every non-padding feature id is in the mirrored
        hot set. A stale mirror is refreshed FIRST (never answering from
        stale values), then consulted."""
        ids = np.asarray(ids, np.int32)
        vals = np.asarray(vals, np.float32)
        with self._lock:
            if not self._fresh():
                if self._ids is not None:
                    if self._mirror_step != int(self.engine.state.step):
                        self.metrics.count("cache_step_refreshes")
                    else:
                        self.metrics.count("cache_stale_refreshes")
                self._refresh_locked()
            self._lookups_since_refresh += 1
            table_ids, table_vals = self._ids, self._vals
        flat = ids.reshape(-1)
        pos = np.searchsorted(table_ids, flat)
        pos = np.clip(pos, 0, len(table_ids) - 1)
        found = (table_ids[pos] == flat) & (flat >= 0)
        if not np.all(found | (flat < 0)):
            self.metrics.count("cache_misses")
            return None
        theta = np.where(found, table_vals[pos], np.float32(0.0)) \
            .astype(np.float32).reshape(ids.shape)
        probs = np.asarray(_hit_predict(vals, theta))
        self.metrics.count("cache_hits")
        return probs
