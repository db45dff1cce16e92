"""Real multi-process execution: one global mesh over N OS processes.

Everything multi-host in this repo used to be single-process *emulation*
(`launch/train.py --hosts H --host-id h`: one process serving one host's
shard of the data plane). This module stands up the real thing — N
processes, one `jax.distributed` coordinator, one GLOBAL mesh whose
devices span every process — while keeping the training loop, the
`ShardAssignment` data plane, and the checkpoint story unchanged.

The CPU recipe (verified on this container's jax/jaxlib):

  1. every process forces its LOCAL device count *before* jax initializes
     (`XLA_FLAGS=--xla_force_host_platform_device_count=<local>`; 4 global
     devices over 2 processes = 2 local devices each);
  2. CPU collectives go through gloo — but ONLY when `num_processes > 1`:
     setting `jax_cpu_collectives_implementation` in a single-process run
     breaks backend init (the CPU client then demands a distributed
     client that does not exist);
  3. `jax.distributed.initialize(coordinator, num_processes, process_id)`
     before the first computation; process 0 hosts the coordinator.

Data flows exactly as the ownership plane prescribes: process h *is*
data-plane host h — its `ShardedLoader` materializes only the batches
`ShardAssignment` assigns to host h, and `global_batch_placement` glues
the per-host rows into one global array per step
(`jax.make_array_from_process_local_data`): process h's local devices
hold rows `[h*B, (h+1)*B)` of the `H*B`-row global batch, the same rows
the single-process emulation concatenates. That is why a real H-process
run is bit-identical (final parameters, deterministic eval) to
`--hosts H --host-id -1` emulation at the same geometry: the jitted step
sees the same global arrays under the same sharding either way. (The
`pmean` loss *metric* may differ by ~1 ulp on a few steps — cross-process
reduction order — which is why parity checks hash parameters, not the
step-path metric; see docs/DISTRIBUTED.md.)
"""
from __future__ import annotations

import dataclasses
import os

__all__ = ["ProcessContext", "initialize", "context", "is_primary",
           "host_value", "barrier", "global_batch_placement",
           "emulate_all_hosts"]

_CONTEXT: "ProcessContext | None" = None


@dataclasses.dataclass(frozen=True)
class ProcessContext:
    """What `initialize` established (or the single-process default)."""

    num_processes: int
    process_id: int
    local_device_count: int
    coordinator: str = ""

    @property
    def is_distributed(self) -> bool:
        return self.num_processes > 1

    @property
    def is_primary(self) -> bool:
        """Process 0 — the coordinator host and the only checkpoint writer."""
        return self.process_id == 0


def _force_local_device_count(n: int) -> None:
    """Pin this process's emulated CPU device count. Must run before jax
    initializes a backend — the flag is read once at backend init."""
    import jax

    flag = f"--xla_force_host_platform_device_count={n}"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        flags = " ".join(f for f in flags.split()
                         if "xla_force_host_platform_device_count" not in f)
    os.environ["XLA_FLAGS"] = (flags + " " + flag).strip()
    backends = getattr(getattr(jax, "_src", None), "xla_bridge", None)
    if backends is not None and getattr(backends, "_backends", None):
        raise RuntimeError(
            "multiprocess.initialize(local_device_count=...) must run "
            "before the first jax computation — the backend is already "
            "initialized and XLA_FLAGS can no longer take effect")


def _check_local_device_count(n: int) -> None:
    """The forced count only exists on the CPU backend: on an accelerator
    the flag does nothing, so a request it cannot honour raises instead of
    running on whatever devices the process happens to see."""
    import jax

    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"local_device_count={n} emulates CPU devices, but this "
            f"process's backend is {backend!r}; on an accelerator every "
            "process uses the devices it sees — drop the option")
    if jax.local_device_count() != n:
        raise RuntimeError(
            f"local_device_count={n} requested, but the CPU backend has "
            f"{jax.local_device_count()} local devices")


def initialize(coordinator: str = "", num_processes: int = 1,
               process_id: int = 0,
               local_device_count: int | None = None) -> ProcessContext:
    """Bootstrap this process's slice of the global runtime.

    Single-process (`num_processes == 1`): optionally pins the emulated
    device count and does NOT touch the collectives config (see module
    docstring, step 2). Multi-process: configures gloo and joins the
    coordinator at `coordinator` ("host:port"; process 0 serves it).
    `local_device_count` is a CPU emulation knob: it raises when the
    initialized backend is not the CPU or did not take the count.
    Idempotent per process; returns the `ProcessContext` that `context()`
    will keep handing out.
    """
    global _CONTEXT
    if _CONTEXT is not None:
        return _CONTEXT
    if local_device_count is not None:
        _force_local_device_count(local_device_count)
    import jax

    if num_processes > 1:
        if not coordinator:
            raise ValueError("num_processes > 1 needs a coordinator "
                             "address (host:port)")
        if not 0 <= process_id < num_processes:
            raise ValueError(f"process_id {process_id} out of range for "
                             f"{num_processes} processes")
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
    if local_device_count is not None:
        _check_local_device_count(local_device_count)
    _CONTEXT = ProcessContext(num_processes=int(num_processes),
                              process_id=int(process_id),
                              local_device_count=jax.local_device_count(),
                              coordinator=coordinator)
    return _CONTEXT


def context() -> ProcessContext:
    """The active context: whatever `initialize` established, else a
    default reflecting jax's own view (always 1 process in runs that never
    called `initialize`)."""
    if _CONTEXT is not None:
        return _CONTEXT
    import jax

    return ProcessContext(num_processes=jax.process_count(),
                          process_id=jax.process_index(),
                          local_device_count=jax.local_device_count())


def is_primary() -> bool:
    """True on the single process that owns externally-visible side
    effects (checkpoint writes, log lines meant to appear once)."""
    import jax

    return jax.process_index() == 0


def host_value(x):
    """Fetch any array — process-local or global — to host memory as
    numpy, on EVERY process.

    Single-process (and fully-replicated global) arrays are a plain
    `device_get`; a global array sharded across processes is gathered
    with `multihost_utils.process_allgather` (collective: all processes
    must call this together). This is the one seam checkpointing and
    `predict` need to work unchanged under real multi-process execution.
    """
    import jax
    import numpy as np

    if isinstance(x, jax.Array) and not x.is_fully_addressable \
            and not x.sharding.is_fully_replicated:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(jax.device_get(x))


def barrier(name: str = "repro_barrier") -> None:
    """Cross-process sync point (no-op single-process)."""
    import jax

    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


def global_batch_placement(mesh, num_processes: int | None = None):
    """Placement callable for a `ShardedLoader` in a real H-process run.

    Each process's loader serves B host-local rows per step; the returned
    callable assembles them into H*B-row GLOBAL arrays sharded over all
    mesh axes — process h's rows land on its own local devices at offset
    h*B (`ShardAssignment.global_rows`), matching the concatenation order
    of the single-process emulation. The arrays carry the exact
    `NamedSharding` the engine's `put_batch` targets, so they pass through
    placement untouched. Safe to call from the loader's prefetch thread
    (`make_array_from_process_local_data` is process-local, not a
    collective).
    """
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    h = jax.process_count() if num_processes is None else num_processes
    sharding = NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))
    if h == 1:
        return lambda batch: batch      # emulation: put_batch places it

    def place(batch: dict) -> dict:
        out = {}
        for k, v in batch.items():
            local = np.asarray(v)
            out[k] = jax.make_array_from_process_local_data(
                sharding, local, (local.shape[0] * h,) + local.shape[1:])
        return out

    return place


class _AllHostsSource:
    """The parity baseline: one process serving EVERY host's stream.

    `batch(s)` concatenates `src.batch(s*H + h)` for h = 0..H-1 — exactly
    the global batch a real H-process run assembles at step s (stride
    ownership: host h owns batches h, h+H, ...). Chunk-owned file corpora
    interleave differently per host and have no single-stream equivalent;
    use a real multi-process run for those.
    """

    def __init__(self, source, num_hosts: int):
        kind = getattr(source, "owned_shards", None)
        if kind is not None and \
                source.owned_shards(0, num_hosts).kind != "stride":
            raise ValueError(
                "all-hosts emulation is defined for stride-owned sources "
                "only; chunk-owned corpora need a real multi-process run")
        self.source = source
        self.num_hosts = int(num_hosts)
        self.batch_size = source.batch_size * self.num_hosts
        self.num_batches = source.num_batches // self.num_hosts

    def batch(self, index: int) -> dict:
        import numpy as np

        parts = [self.source.batch(index * self.num_hosts + h)
                 for h in range(self.num_hosts)]
        return {k: np.concatenate([np.asarray(p[k]) for p in parts])
                for k in parts[0]}


def emulate_all_hosts(source, num_hosts: int):
    """Wrap a stride-owned `DataSource` so one process serves the
    concatenated per-step global batch of all `num_hosts` hosts
    (`launch/train.py --hosts H --host-id -1`)."""
    return _AllHostsSource(source, num_hosts)
