"""Paper §4 analogue: Zipf-head handling vs shuffle skew.

The paper splits high-frequency features into sub-features so no reducer's
line exceeds a block; our adaptation replicates the head. This benchmark
sweeps the hot-set size and reports (a) capacity-overflow count at a tight
capacity factor, (b) the max/mean owner-load imbalance, (c) effective a2a
bytes — the three faces of the same skew.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.api import get_strategy
from repro.api.strategies import StrategyContext
from repro.core import hot_sharding, sparse


def run(f: int = 1 << 16, p: int = 64, n: int = 1 << 15,
        zipf_alpha: float = 1.1, cap_factor: float = 1.5):
    rng = np.random.default_rng(0)
    raw = rng.zipf(zipf_alpha, size=n).astype(np.int64)
    ids_np = (((raw - 1) % f) * np.int64(2654435761) % f).astype(np.int32)
    ids = jnp.asarray(ids_np)
    block = f // p
    # capacity sized against the UNIQUE mean (the combiner dedups), so the
    # Zipf head's owner is the one that overflows
    uniq = len(np.unique(ids_np))
    mean = max(1, uniq // p)
    cap = max(16, int(cap_factor * mean))

    rows = []
    for max_hot in (0, 16, 64, 256, 1024):
        if max_hot:
            hot = jnp.asarray(hot_sharding.select_hot(ids_np, 1e-4, max_hot))
            _, is_hot, cold = hot_sharding.split_hot(ids, hot)
            n_hot = int(jnp.sum(is_hot))
        else:
            cold, n_hot = ids, 0
        r = sparse.route_build(cold, p, block, cap)
        imb = float(hot_sharding.load_imbalance(cold, p, block))
        ctx = StrategyContext(axes=(), num_shards=p, block_size=block,
                              capacity=cap)
        a2a_bytes = get_strategy("a2a").bytes_per_device(ctx).total
        rows.append({"max_hot": max_hot, "hot_hits": n_hot,
                     "overflow": int(r.overflow), "imbalance": imb,
                     "a2a_bytes": a2a_bytes})
    return rows


def main():
    rows = run()
    print(f"{'max_hot':>8s} {'hot_hits':>9s} {'overflow':>9s} "
          f"{'imbalance':>10s} {'a2a_bytes':>10s}")
    for r in rows:
        print(f"{r['max_hot']:>8d} {r['hot_hits']:>9d} {r['overflow']:>9d} "
              f"{r['imbalance']:>10.2f} {r['a2a_bytes']:>10d}")
    return rows


if __name__ == "__main__":
    main()
