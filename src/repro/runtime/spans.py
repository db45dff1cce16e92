"""The program's host spans and counters.

A span times a region of host code twice over: it writes a
`jax.profiler.TraceAnnotation`, which lands on the profiler's host plane
on the same clock as the device's ops, and it adds its `perf_counter`
seconds and a count to a process-wide table that `totals()` reads. A
counter adds to the same table. There is no switch: with no profiler
session active the annotation is a no-op in C++, and the table costs a
lock and two additions.

    from repro.runtime import spans

    with spans.step_span("dpmr.train_step", step):
        with spans.span("dpmr.dispatch"):
            ...
    spans.count("dpmr.steps")
    spans.totals()   # {"spans": {name: {"s": ..., "n": ...}},
                     #  "counts": {name: n}}

Names start with `dpmr.` (the engine) or `loader.` (the data plane);
`bench.` is the benchmark's own prefix and no program span takes it.

  dpmr.train_step     step span: one `DPMREngine.train_step`
  dpmr.dispatch         the step-fn lookup, batch placement and the jitted
                        call up to its return
  dpmr.metrics_sync     the host reads of the step's loss, accuracy and
                        overflow, which wait for the device
  loader.wait         `ShardedLoader`'s consumer waiting on its queue
  loader.place        the producer's load and placement of one batch
                      (its cursor position as `epoch` and `step`)

  dpmr.steps, dpmr.step_fns_built (a compile follows), dpmr.overflow
  (features dropped by the exchange's capacity), loader.batches,
  loader.starved (a wait that found the queue empty)
"""
from __future__ import annotations

import collections
import threading
import time

from jax.profiler import StepTraceAnnotation, TraceAnnotation

_lock = threading.Lock()
_seconds: collections.Counter = collections.Counter()
_spans: collections.Counter = collections.Counter()
_counts: collections.Counter = collections.Counter()


class span:
    """`with span(name, **args):` a host span; `args` go into the trace."""

    __slots__ = ("name", "_note", "_t")
    _annotation = TraceAnnotation

    def __init__(self, name: str, **args):
        self.name = name
        self._note = self._annotation(name, **args)

    def __enter__(self):
        self._note.__enter__()
        self._t = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t
        self._note.__exit__(*exc)
        with _lock:
            _seconds[self.name] += dt
            _spans[self.name] += 1


class step_span(span):
    """A span that XProf's step view groups as step `step`."""

    __slots__ = ()
    _annotation = StepTraceAnnotation

    def __init__(self, name: str, step: int):
        super().__init__(name, step_num=step)


def count(name: str, n: int = 1) -> None:
    with _lock:
        _counts[name] += n


def totals() -> dict:
    """Seconds and count of every span, and every counter, since the
    last `reset()`."""
    with _lock:
        return {"spans": {k: {"s": _seconds[k], "n": _spans[k]}
                          for k in _spans},
                "counts": dict(_counts)}


def reset() -> None:
    with _lock:
        _seconds.clear()
        _spans.clear()
        _counts.clear()
