"""Pieces every driver shares: seeds, spans, the compile counter, the
device's own readings, and the check of compared numbers against limits."""
from __future__ import annotations

import collections
import contextlib
import importlib.util
import os
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(*parts: str) -> dict:
    import json

    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """bench/<kind>/<name>.py as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_config(config: dict):
    """The program's configuration object for a configuration file."""
    from repro.configs.base import DPMRConfig

    return DPMRConfig(**config["model"])


def sub_seed(seed: int, *tags: int) -> int:
    """A 32-bit seed for one purpose of a run, from the run's --seed (any
    size) and the purpose's tags."""
    return int(np.random.SeedSequence([int(seed), *tags])
               .generate_state(1)[0])


class Spans:
    """The benchmark's own host spans around calls into the program:
    total seconds and count per name. With `annotate`, each span is also
    written into the profiler's trace, so that idle gaps on the device can
    be put down to what the host was doing."""

    def __init__(self, annotate: bool = False):
        self.total = collections.Counter()
        self.count = collections.Counter()
        self._annotate = annotate

    @contextlib.contextmanager
    def __call__(self, name: str):
        if self._annotate:
            import jax

            ctx = jax.profiler.TraceAnnotation(name)
        else:
            ctx = contextlib.nullcontext()
        t = time.perf_counter()
        with ctx:
            yield
        self.total[name] += time.perf_counter() - t
        self.count[name] += 1

    def as_dict(self) -> dict:
        return {k: {"s": self.total[k], "n": self.count[k]}
                for k in self.total}


class Phases:
    """Seconds of each part of set-up, each from the end of the one
    before: `phase(name)` closes the part called `name`."""

    def __init__(self, into: dict):
        self.into = into
        self.t = time.perf_counter()

    def __call__(self, name: str):
        now = time.perf_counter()
        self.into[name] = now - self.t
        self.t = now


class CompileCounter:
    """Counts backend compilations and persistent-cache reads while on."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            self.n += 1


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """peak_bytes_in_use on the fullest local device."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def judge(checks: dict[str, tuple[float, float]]) -> bool:
    """Each compared number must lie within its limit."""
    return all(np.isfinite(v) and v <= lim for v, lim in checks.values())
