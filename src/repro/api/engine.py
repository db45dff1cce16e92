"""`DPMREngine` — the typed façade over the DPMR sparse core.

One object owns the compiled step functions (`StepFns`), the sharded
`DPMRState`, host→device batch placement, the optimizer/schedule selection,
and the checkpoint story:

    from repro.api import DPMREngine

    eng = DPMREngine(cfg, mesh, hot_ids=hot)
    eng.fit_sgd(batches, steps=100)        # minibatch SGD
    eng.fit(batch_iter_fn)                 # paper-regime full-batch GD
    probs = eng.predict(batch)
    metrics = eng.evaluate(test_batches)
    eng.save("/ckpt/dir"); eng.restore("/ckpt/dir")

The data arguments of `fit` / `fit_sgd` / `evaluate` accept, besides plain
iterables, anything from the `repro.data` plane: a `ShardedLoader`, a
`DataSource`, or a registered source name + spec kwargs —

    eng.fit_sgd("zipf_sparse", steps=40,
                spec=dict(batch_size=512, num_features=1 << 14))

A loader's resumable cursor rides along in `save()` / `restore()` extras, so
a restored engine + loader continues the exact batch stream an uninterrupted
run would have seen.

Step functions are compiled lazily per global batch size and LRU-cached
(`max_cached_fns`), so one engine serves training and differently-sized eval
batches without retaining every compilation forever. The distribution
strategy (`cfg.distribution`) is resolved through the registry in
`repro.api.strategies`.

The updating steps donate the consumed state (`core.dpmr.StepFns`), so
`engine.state` always points at live buffers but any OLD reference to it
dies with the next `train_step`/`fit`; snapshot with
`jax.tree.map(jnp.copy, engine.state)` if you need a pre-step copy.
"""
from __future__ import annotations

from collections.abc import Callable, Iterable
import itertools
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.strategies import list_strategies
from repro.ckpt.checkpointer import Checkpointer
from repro.configs.base import DPMRConfig
from repro.core import dpmr, hot_sharding
from repro.core.dpmr import StepFns
from repro.data import DataSource, ShardedLoader, get_source
from repro.data.loader import put_sharded
from repro.kernels import ops
from repro.runtime import multiprocess, spans


def put_batch(batch: dict, mesh) -> dict:
    """Host→device placement: every batch leaf sharded over all mesh axes.

    Delegates to `repro.data.loader.put_sharded` — the single definition the
    ShardedLoader's "sharded" placement also uses — so leaves a loader
    already placed pass through untouched."""
    return put_sharded(batch, mesh)


def binary_prf_metrics(predict_fn: Callable[[dict], np.ndarray],
                       test_batches: Iterable[dict]) -> dict:
    """Fig. 1 metrics: per-class precision/recall/F + macro average.

    `predict_fn(batch) -> probs`; batches must carry "labels".
    """
    tp = fp = fn_ = tn = 0
    for batch in test_batches:
        pred = (predict_fn(batch) >= 0.5).astype(np.int32)
        y = np.asarray(batch["labels"])
        tp += int(np.sum((pred == 1) & (y == 1)))
        fp += int(np.sum((pred == 1) & (y == 0)))
        fn_ += int(np.sum((pred == 0) & (y == 1)))
        tn += int(np.sum((pred == 0) & (y == 0)))

    def prf(tp, fp, fn):
        p = tp / max(tp + fp, 1)
        r = tp / max(tp + fn, 1)
        f = 2 * p * r / max(p + r, 1e-9)
        return p, r, f

    p1, r1, f1 = prf(tp, fp, fn_)
    p0, r0, f0 = prf(tn, fn_, fp)
    return {
        "precision_pos": p1, "recall_pos": r1, "f_pos": f1,
        "precision_neg": p0, "recall_neg": r0, "f_neg": f0,
        "precision_avg": (p1 + p0) / 2, "recall_avg": (r1 + r0) / 2,
        "f_avg": (f1 + f0) / 2,
    }


def hot_ids_from_corpus(cfg: DPMRConfig, sample_batches: Iterable[dict],
                        mesh) -> np.ndarray:
    """initParameters-time frequency statistics -> the hot set, counted on
    the host over the sample's ids (no (F,) histogram on a device)."""
    ids = np.concatenate([np.asarray(b["ids"]).reshape(-1)
                          for b in sample_batches])
    return hot_sharding.select_hot(ids, cfg.hot_threshold, cfg.max_hot)


class DPMREngine:
    """Typed façade: state + compiled steps + checkpointing for sparse DPMR.

    Parameters
    ----------
    cfg:         DPMRConfig (features, strategy, optimizer, schedule, ...)
    mesh:        jax Mesh; every device is one DPMR node (samples + params)
    kernel_impl: hot-path lowering ("xla" | "pallas" | "pallas_interpret",
                 see repro.kernels.ops.KERNEL_IMPLS): the computeGradients
                 map body plus the routing kernels behind
                 StrategyContext.kernel_impl. None defers to
                 cfg.kernel_impl.
    cap_factor:  a2a capacity factor (slots per (src,dst) pair = cap_factor
                 x the uniform mean)
    hot_ids:     replicated Zipf-head ids (see `hot_ids_from_corpus`); None
                 disables hot replication
    state:       resume from an existing DPMRState instead of zeros
    max_cached_fns: LRU bound on the per-batch-size StepFns cache (bucketed
                 serving traffic would otherwise compile and retain one
                 entry per distinct batch size forever)
    """

    def __init__(self, cfg: DPMRConfig, mesh, *,
                 kernel_impl: str | None = None,
                 cap_factor: float = 4.0, hot_ids=None,
                 state: dpmr.DPMRState | None = None,
                 max_cached_fns: int = 8):
        self.cfg = cfg
        self.mesh = mesh
        self.kernel_impl = ops.normalize_impl(
            cfg.kernel_impl if kernel_impl is None else kernel_impl)
        self.cap_factor = cap_factor
        if max_cached_fns < 1:
            raise ValueError(f"max_cached_fns must be >= 1: {max_cached_fns}")
        self.max_cached_fns = max_cached_fns
        self._fns: dict[int, StepFns] = {}
        self._checkpointers: dict[str, Checkpointer] = {}
        self._loader: ShardedLoader | None = None
        self._steps = 0      # train_step calls: the step spans' numbers
        self._schedule = dpmr.make_schedule(cfg)
        with jax.set_mesh(mesh):
            self.state = state if state is not None else dpmr.init_state(
                cfg, mesh, hot_ids)

    # -- step-function compilation cache ------------------------------------

    def step_fns(self, batch_size: int) -> StepFns:
        """Compiled StepFns for a given GLOBAL batch size (LRU-cached)."""
        fns = self._fns.pop(batch_size, None)
        if fns is None:
            spans.count("dpmr.step_fns_built")
            with jax.set_mesh(self.mesh):
                fns = dpmr.make_step_fns(
                    self.cfg, self.mesh, batch_size,
                    kernel_impl=self.kernel_impl,
                    cap_factor=self.cap_factor)
        self._fns[batch_size] = fns     # move to the end: most recently used
        while len(self._fns) > self.max_cached_fns:
            self._fns.pop(next(iter(self._fns)))     # evict least recent
        return fns

    @property
    def fns(self) -> StepFns:
        """StepFns of the most recently used batch size."""
        if not self._fns:
            raise RuntimeError("no step fns compiled yet; run a step or "
                               "call engine.step_fns(batch_size)")
        return next(reversed(self._fns.values()))

    def put_batch(self, batch: dict) -> dict:
        return put_batch(batch, self.mesh)

    def learning_rate(self) -> float:
        """Schedule value at the current step."""
        return float(self._schedule(jnp.asarray(self.state.step)))

    # -- data-plane resolution ----------------------------------------------

    def _as_loader(self, data, spec: dict | None) -> \
            ShardedLoader | None:
        """Normalize a data argument to a ShardedLoader when it comes from
        the data plane (loader | DataSource | registered source name);
        returns None for plain iterables/callables."""
        # engine-built loaders are pinned to a single stream (host 0 of 1):
        # every process must place identical global batches under the mesh
        # sharding; per-host disjoint shards need global-array placement —
        # build your own ShardedLoader for that (cf. launch/train.make_loader)
        if isinstance(data, str):
            return ShardedLoader(get_source(data, **(spec or {})), self.mesh,
                                 host_index=0, num_hosts=1)
        if spec is not None:
            # anything non-str never reads spec — dropping it silently would
            # train on a differently-configured source than the caller asked
            raise TypeError("spec= is only meaningful with a source NAME; "
                            f"got {type(data).__name__} — configure the "
                            "source/loader directly instead")
        if isinstance(data, ShardedLoader):
            return data
        # duck-typed sources count too: register_source only requires
        # batch(index) / batch_size / num_batches, not the base class
        if isinstance(data, DataSource) or (
                hasattr(data, "batch") and hasattr(data, "batch_size")
                and hasattr(data, "num_batches")):
            return ShardedLoader(data, self.mesh, host_index=0, num_hosts=1)
        return None

    # -- training -----------------------------------------------------------

    def train_step(self, batch: dict) -> dict:
        """One minibatch update; returns host-side metrics."""
        self._steps += 1
        with spans.step_span("dpmr.train_step", self._steps):
            with spans.span("dpmr.dispatch"):
                fns = self.step_fns(len(batch["labels"]))
                with jax.set_mesh(self.mesh):
                    self.state, m = fns.train_step(self.state,
                                                   self.put_batch(batch))
            with spans.span("dpmr.metrics_sync"):
                out = {"loss": float(m["loss"]),
                       "accuracy": float(m["accuracy"]),
                       "overflow": int(m["overflow"])}
        spans.count("dpmr.steps")
        spans.count("dpmr.overflow", out["overflow"])
        return out

    def fit_sgd(self, data, steps: int | None = None, *,
                spec: dict | None = None) -> list[dict]:
        """Minibatch SGD (one update per batch); returns the history.

        `data`: iterable of batches, a `ShardedLoader`, a `DataSource`, or a
        registered source name (+ `spec` kwargs). With a loader, batches
        arrive prefetched/pre-placed and its cursor tracks progress for
        exact resume; `steps` bounds the number of updates. `steps=None` on
        a bounded loader trains the remainder of the current epoch (one
        corpus pass, the legacy generator behaviour); on an unbounded one
        it is an error rather than an infinite loop."""
        loader = self._as_loader(data, spec)
        if loader is not None:
            self._loader = loader
            if steps is None and loader.steps_per_epoch is None:
                raise ValueError(
                    "fit_sgd over an unbounded loader needs steps= (or give "
                    "the loader an epoch_size)")
            batches = loader.batches(steps) if steps is not None \
                else loader.epoch()
        else:
            batches = iter(data) if steps is None else \
                itertools.islice(iter(data), steps)
        history: list[dict] = []
        base = int(self.state.step)   # continue numbering across resumes
        for i, batch in enumerate(batches):
            m = self.train_step(batch)
            history.append({"step": base + i + 1, **m})
        return history

    def fit(self, data, iterations: int | None = None,
            eval_fn: Callable[["DPMREngine"], dict] | None = None, *,
            spec: dict | None = None) -> list[dict]:
        """Full-batch gradient descent: one update per ITERATION over the
        whole corpus (the paper's regime).

        `data`: a callable yielding the corpus in fixed-size batches each
        time it is called (legacy `batch_iter_fn`), or a `ShardedLoader` /
        `DataSource` / source name (+ `spec`) — then each iteration consumes
        one FULL loader epoch (a mid-epoch cursor is rewound to its epoch
        start, so every update averages the whole corpus as the paper
        regime requires; the cursor's epoch field counts iterations)."""
        loader = self._as_loader(data, spec)
        if loader is not None:
            self._loader = loader
            batch_iter_fn = lambda: loader.epoch(from_start=True)  # noqa: E731
        elif callable(data):
            batch_iter_fn = data
        else:
            raise TypeError(
                "fit() needs a batch_iter_fn callable, a ShardedLoader, a "
                f"DataSource, or a source name; got {type(data).__name__}")
        iterations = self.cfg.iterations if iterations is None else iterations
        history: list[dict] = []
        for it in range(iterations):
            acc_cold = jnp.zeros_like(self.state.cold)
            acc_hot = jnp.zeros_like(self.state.hot)
            tot_loss = tot_acc = 0.0
            nb = 0
            with jax.set_mesh(self.mesh):
                for batch in batch_iter_fn():
                    fns = self.step_fns(len(batch["labels"]))
                    gc, gh, m = fns.grad_step(self.state,
                                              self.put_batch(batch))
                    acc_cold = acc_cold + gc
                    acc_hot = acc_hot + gh
                    tot_loss += float(m["loss"])
                    tot_acc += float(m["accuracy"])
                    nb += 1
                if nb == 0:
                    raise ValueError(
                        "fit(): the corpus yielded no batches in iteration "
                        f"{it + 1} — an empty batch_iter_fn()/loader epoch "
                        "cannot produce an update")
                self.state = fns.apply_update(
                    self.state, acc_cold / nb, acc_hot / nb,
                    self.learning_rate())
            rec = {"iteration": it + 1, "loss": tot_loss / nb,
                   "accuracy": tot_acc / nb}
            if eval_fn is not None:
                rec.update(eval_fn(self))
            history.append(rec)
        return history

    # -- inference ----------------------------------------------------------

    def predict(self, batch: dict) -> np.ndarray:
        """Algorithm 9: probabilities for a test batch ({ids, vals}).

        Compiles (and LRU-caches) StepFns for this EXACT batch size — ad-hoc
        caller-shaped batches each cost a compilation and can thrash the
        cache under mixed request sizes. Serving paths should use
        `predict_padded`, which pads to a small ladder of bucketed sizes so
        the cache gets hits instead of recompiles."""
        fns = self.step_fns(len(batch["ids"]))
        with jax.set_mesh(self.mesh):
            probs = fns.predict(self.state, self.put_batch(
                {k: batch[k] for k in ("ids", "vals")}))
        # host_value, not np.asarray: under real multi-process execution
        # the result is a global array spanning processes, and every
        # process gets the full probability vector (collective gather)
        return multiprocess.host_value(probs)

    def bucket_for(self, n: int, buckets: Iterable[int] | None = None) -> int:
        """The padded batch size `predict_padded` would run `n` rows at.

        Default ladder: the smallest power-of-two multiple of the mesh shard
        count P that holds `n` (P, 2P, 4P, ...) — at most log2(max_batch)
        distinct compilations ever. An explicit `buckets` ladder must be
        multiples of P; `n` above the largest bucket is an error (split the
        batch instead of silently compiling an unplanned size)."""
        p = dpmr.num_shards(self.mesh)
        if n <= 0:
            raise ValueError(f"batch size must be positive: {n}")
        if buckets is None:
            return p * (1 << (-(-n // p) - 1).bit_length())
        for b in sorted(set(buckets)):
            if b % p:
                raise ValueError(
                    f"bucket {b} is not a multiple of the mesh shard "
                    f"count {p}")
            if b >= n:
                return b
        raise ValueError(
            f"batch of {n} rows exceeds the largest bucket in "
            f"{sorted(set(buckets))}")

    def predict_padded(self, batch: dict,
                       buckets: Iterable[int] | None = None) -> np.ndarray:
        """`predict` with the batch padded to a bucketed size, results
        sliced back to the caller's rows.

        Padding rows are empty samples (ids=-1, vals=0), which route nowhere
        and add no owner load, so the first `n` probabilities are
        bit-identical to `predict(batch)` — but every bucketed size hits the
        per-batch-size StepFns LRU cache instead of compiling a fresh entry
        per distinct request size. This is the serving predict path
        (`repro.serve.DPMRServeEngine` coalesces requests into it)."""
        ids = np.asarray(batch["ids"])
        vals = np.asarray(batch["vals"])
        n = len(ids)
        b = self.bucket_for(n, buckets)
        if b != n:
            pad = b - n
            ids = np.concatenate(
                [ids, np.full((pad, ids.shape[1]), -1, ids.dtype)])
            vals = np.concatenate(
                [vals, np.zeros((pad, vals.shape[1]), vals.dtype)])
        return self.predict({"ids": ids, "vals": vals})[:n]

    def evaluate(self, test_batches, *, spec: dict | None = None) -> dict:
        """Fig. 1 metrics: per-class precision/recall/F + macro average.

        `test_batches`: iterable of batches, or a `ShardedLoader` /
        `DataSource` / source name (+ `spec`) — then one full epoch of the
        test source is scored, and the loader's cursor is left exactly
        where it was (repeatable, and safe on a training loader whose
        resume position save() will persist)."""
        loader = self._as_loader(test_batches, spec)
        if loader is None:
            return binary_prf_metrics(self.predict, test_batches)
        mark = loader.cursor
        try:
            return binary_prf_metrics(self.predict,
                                      loader.epoch(from_start=True))
        finally:
            loader.seek(mark)

    # -- checkpointing -------------------------------------------------------

    def _checkpointer(self, directory: str, keep: int = 3) -> Checkpointer:
        """One long-lived Checkpointer per directory: `save(block=False)`
        hands its write thread to an object that survives until the next
        save (which joins it) — a throwaway instance per call would orphan
        the thread and allow two concurrent writers."""
        ck = self._checkpointers.get(directory)
        if ck is None:
            ck = self._checkpointers[directory] = Checkpointer(
                directory, keep=keep)
        ck.keep = keep
        return ck

    def wait_saves(self) -> None:
        """Join any in-flight async checkpoint writes (call before process
        exit; `save(block=True)` and every subsequent save also join)."""
        for ck in self._checkpointers.values():
            ck.wait()

    def save(self, directory: str, *, keep: int = 3, block: bool = True,
             loader: ShardedLoader | None = None) -> int:
        """Atomic checkpoint of the sparse state; returns the step saved.

        `block=False` keeps only the device->host snapshot on the step
        path and serializes/fsyncs on a background thread (the snapshot is
        taken before returning, so the training loop may immediately
        mutate/donate the live state). Under real multi-process execution
        every process must call this (the gather is collective); only
        process 0 writes.

        The data cursor of `loader` (default: the last loader handed to
        fit/fit_sgd) is persisted in the manifest extras, so restore resumes
        the exact batch stream."""
        loader = loader if loader is not None else self._loader
        step = int(self.state.step)
        # record the RESOLVED strategy name: under cfg.distribution="auto"
        # the carry in DPMRState.strat belongs to whatever the autotuner
        # picked, and a restore must be able to name (and check) it
        extra = {"kind": "dpmr_sparse",
                 "distribution": dpmr.resolve_distribution(self.cfg,
                                                           self.mesh),
                 "topk_frac": self.cfg.topk_frac,
                 "optimizer": self.cfg.optimizer,
                 "num_features": self.cfg.num_features}
        if loader is not None:
            extra["data"] = loader.state_dict()
        self._checkpointer(directory, keep).save(
            step, self.state, block=block, extra=extra)
        return step

    def restore(self, directory: str, step: int | None = None, *,
                loader: ShardedLoader | None = None,
                on_host_change: str = "error") -> dict:
        """Restore state in place (latest step by default); returns the
        checkpoint manifest. Leaves are placed under the engine's current
        shardings, so restoring onto a different mesh re-shards (for a mesh
        with a different shard count, re-pad via runtime/elastic.py).

        If the checkpoint carries a data cursor and a loader is available
        (`loader=` or the engine's attached one), the loader is sought to
        it — training continues on the exact next batch.
        `on_host_change="reassign"` accepts a cursor recorded under a
        different data-plane host count: shard ownership is recomputed for
        the new geometry and the stream resumes at the epoch boundary
        (mirrors the strategy-carry reset on elastic mesh rescale).

        If the checkpoint was written at a DIFFERENT total shard count
        (the cold table's padded length no longer matches this engine's
        mesh), the state is re-padded/re-sharded through
        `runtime/elastic.py::reshard_dpmr_state` instead of being placed
        blind — the elastic-restart path (the strategy carry resets; the
        hot-set geometry, cfg.max_hot, must match)."""
        ck = self._checkpointer(directory, keep=3)
        with jax.set_mesh(self.mesh):
            arrs, manifest = ck.restore_host(step)
            leaves, treedef = jax.tree.flatten(self.state)
            if len(arrs) != len(leaves):
                raise ValueError(
                    f"checkpoint has {len(arrs)} leaves, the engine state "
                    f"{len(leaves)} — not a {manifest['extra'].get('kind')} "
                    "checkpoint for this state structure")
            if [tuple(s) for s in manifest["shapes"]] == \
                    [tuple(l.shape) for l in leaves]:
                # scalar leaves (step) may live uncommitted on one device;
                # device_putting them under that SingleDeviceSharding would
                # COMMIT them there and conflict with the mesh-sharded
                # table in the next jitted step — replicate instead
                from jax.sharding import NamedSharding, PartitionSpec

                rep = NamedSharding(self.mesh, PartitionSpec())
                self.state = jax.tree.unflatten(treedef, [
                    jax.device_put(a, l.sharding
                                   if isinstance(l.sharding, NamedSharding)
                                   else rep)
                    for a, l in zip(arrs, leaves, strict=True)])
            else:
                from repro.runtime.elastic import reshard_dpmr_state

                self.state = reshard_dpmr_state(
                    jax.tree.unflatten(treedef, arrs), self.cfg, self.mesh)
        saved_dist = manifest.get("extra", {}).get("distribution")
        if saved_dist is not None and saved_dist not in list_strategies():
            # a registry KeyError here would name nothing useful; the
            # common culprit is a composition (or other user-registered
            # strategy) from the saving session that this process never
            # re-registered
            raise ValueError(
                f"checkpoint was trained with distribution strategy "
                f"{saved_dist!r}, which is not registered in this "
                "process — register it first (register_strategy / "
                "register_composition, e.g. a session-local composition "
                "does not auto-register on import). Registered: "
                f"{list_strategies()}")
        mine = dpmr.resolve_distribution(self.cfg, self.mesh)
        if saved_dist is not None and saved_dist != mine:
            warnings.warn(
                f"checkpoint was trained with distribution={saved_dist!r} "
                f"but this engine uses {mine!r}; the "
                "persistent strategy carry (DPMRState.strat) may be "
                "meaningless or mis-shaped for the new strategy",
                RuntimeWarning, stacklevel=2)
        saved_frac = manifest.get("extra", {}).get("topk_frac")
        if (mine == "topk_reduce"
                and saved_dist == "topk_reduce"
                and saved_frac is not None
                and saved_frac != self.cfg.topk_frac):
            warnings.warn(
                f"checkpoint carries a topk_reduce residual accumulated at "
                f"topk_frac={saved_frac} but this engine sparsifies at "
                f"{self.cfg.topk_frac}; training stays correct (error "
                "feedback re-injects it) but the first steps flush a "
                "residual sized for the old k",
                RuntimeWarning, stacklevel=2)
        if loader is not None:
            self._loader = loader      # attach even for cursor-less ckpts,
        else:                          # so the NEXT save records a cursor
            loader = self._loader
        data_state = manifest.get("extra", {}).get("data")
        if data_state is not None:
            if loader is not None:
                loader.load_state_dict(data_state,
                                       on_host_change=on_host_change)
            else:
                warnings.warn(
                    "checkpoint carries a data cursor "
                    f"{data_state.get('cursor')} but no loader is attached; "
                    "pass loader= (or seek your loader to this cursor) or "
                    "training will replay already-consumed batches",
                    RuntimeWarning, stacklevel=2)
        return manifest
