"""Checkpointing: atomic, versioned, async-capable, elastic on restore.

Layout:   <dir>/step_<N>/
            manifest.json        # tree structure, shapes, dtypes, step, data state
            arr_<i>.npy          # one file per leaf (full logical array)

Guarantees:
  - atomicity, twice over: leaves land in `step_<N>.tmp` which is
    os.replace'd into place only when complete, and INSIDE the directory
    the manifest itself is written to a temp name, fsync'd, and
    os.replace'd last — so a complete `manifest.json` is the definition
    of a complete checkpoint. Discovery (`all_steps`) only counts step
    directories whose manifest parses: a crash mid-write (or a truncated
    manifest from any other writer) makes that step invisible and restore
    falls back to the previous good one instead of crashing.
  - keep-N retention.
  - elastic restore: leaves are FULL logical arrays; `restore` device_puts
    them under whatever shardings the NEW mesh prescribes, so a run saved on
    a (16,16) mesh restarts on (8,16) or (2,16,16) unchanged (DPMR sparse
    state needs re-padding — runtime/elastic.py; `restore_host` hands back
    the raw host arrays for that path).
  - async: `save(..., block=False)` keeps only the device->host snapshot on
    the step path (the leaves are host copies the moment save() returns, so
    later donation/mutation of the live buffers cannot leak into the file)
    and does serialization + fsync + the atomic renames on a daemon thread;
    `wait()` joins before the next save or process exit, and re-raises a
    write that failed on that thread (so does the next `save`).

Multi-process: under real `jax.distributed` execution every process calls
`save` (the host gather of cross-process arrays is a collective —
`runtime/multiprocess.host_value`), but only process 0 touches the
filesystem; the directory is expected to be shared (or only process 0's
copy is the checkpoint of record). Restore reads full logical arrays on
every process and device_puts them under the global shardings.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import time

import jax
import numpy as np

from repro.runtime import multiprocess


class Checkpointer:
    def __init__(self, directory: str, keep: int = 3):
        self.dir = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._thread: threading.Thread | None = None
        self._error: BaseException | None = None

    # -- save ---------------------------------------------------------------

    def save(self, step: int, state, extra: dict | None = None,
             block: bool = True):
        """Snapshot `state` (pytree of jax/np arrays) at `step`.

        The device->host copy happens HERE, synchronously — that is the
        snapshot point, and the only work `block=False` leaves on the step
        path. Everything after (np.save, manifest fsync, atomic renames,
        GC) runs inline (`block=True`) or on a daemon thread."""
        self.wait()
        leaves, treedef = jax.tree.flatten(state)
        host_leaves = [multiprocess.host_value(l) for l in leaves]
        manifest = {
            "step": int(step),
            "num_leaves": len(leaves),
            "paths": [str(p) for p, _ in
                      jax.tree_util.tree_flatten_with_path(state)[0]],
            "shapes": [list(l.shape) for l in host_leaves],
            "dtypes": [str(l.dtype) for l in host_leaves],
            "extra": extra or {},
            "time": time.time(),
        }
        if not multiprocess.is_primary():
            return      # gather above was the collective part; 0 writes

        def _write():
            final = os.path.join(self.dir, f"step_{step:010d}")
            tmp = final + ".tmp"
            if os.path.exists(tmp):
                shutil.rmtree(tmp)
            os.makedirs(tmp)
            for i, arr in enumerate(host_leaves):
                np.save(os.path.join(tmp, f"arr_{i}.npy"), arr)
            # manifest last, via its own temp + replace: its presence (and
            # parseability) is the completeness marker readers trust
            mtmp = os.path.join(tmp, "manifest.json.tmp")
            with open(mtmp, "w") as f:
                json.dump(manifest, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(mtmp, os.path.join(tmp, "manifest.json"))
            if os.path.exists(final):
                shutil.rmtree(final)
            os.replace(tmp, final)
            self._gc()

        if block:
            _write()
            return

        def _write_async():
            try:
                _write()
            except BaseException as e:  # noqa: BLE001 — re-raised by wait()
                self._error = e

        self._thread = threading.Thread(target=_write_async, daemon=True)
        self._thread.start()

    def wait(self):
        """Join the in-flight background write; a write that failed there
        raises here (and so from the next `save`), never silently."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        err, self._error = self._error, None
        if err is not None:
            raise RuntimeError(
                f"async checkpoint write to {self.dir} failed") from err

    def _gc(self):
        steps = self.all_steps()
        for s in steps[: -self.keep] if self.keep > 0 else []:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:010d}"),
                          ignore_errors=True)

    # -- restore --------------------------------------------------------------

    def _manifest_path(self, step: int) -> str:
        return os.path.join(self.dir, f"step_{step:010d}", "manifest.json")

    def _manifest_ok(self, step: int) -> bool:
        try:
            with open(self._manifest_path(step)) as f:
                json.load(f)
            return True
        except (OSError, ValueError):
            return False

    def all_steps(self) -> list[int]:
        """Steps with a COMPLETE checkpoint (parseable manifest). A dir
        whose manifest is missing or truncated — a crashed writer, a
        partial copy — is skipped, so `restore()` falls back to the
        newest good step instead of crashing on the bad one."""
        out = []
        for name in os.listdir(self.dir):
            if name.startswith("step_") and not name.endswith(".tmp"):
                try:
                    step = int(name[5:])
                except ValueError:
                    continue
                if self._manifest_ok(step):
                    out.append(step)
        return sorted(out)

    def latest_step(self) -> int | None:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore_host(self, step: int | None = None
                     ) -> tuple[list[np.ndarray], dict]:
        """Raw host-side leaves + manifest, no placement — the elastic
        path: when the saved geometry no longer matches the live state
        (`shapes` differ), re-pad/re-shard these with
        `runtime/elastic.py` instead of device_putting them blind."""
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        with open(self._manifest_path(step)) as f:
            manifest = json.load(f)
        d = os.path.join(self.dir, f"step_{step:010d}")
        arrs = [np.load(os.path.join(d, f"arr_{i}.npy"))
                for i in range(manifest["num_leaves"])]
        return arrs, manifest

    def restore(self, like, step: int | None = None,
                shardings=None):
        """Restore into the structure of `like` (pytree). If `shardings` is
        given (pytree of NamedSharding matching `like`), leaves are placed
        under them — this is the elastic-resharding path."""
        arrs, manifest = self.restore_host(step)
        leaves, treedef = jax.tree.flatten(like)
        assert len(leaves) == manifest["num_leaves"], (
            len(leaves), manifest["num_leaves"])
        if shardings is not None:
            sh_leaves = jax.tree.leaves(shardings)
            out = [jax.device_put(a, s) for a, s in zip(arrs, sh_leaves, strict=True)]
        else:
            out = [jax.device_put(a, l.sharding)
                   if isinstance(l, jax.Array) else jax.numpy.asarray(a)
                   for a, l in zip(arrs, leaves, strict=True)]
        return jax.tree.unflatten(treedef, out), manifest


def manifest_extra(directory: str, step: int | None = None) -> dict:
    ck = Checkpointer(directory)
    step = ck.latest_step() if step is None else step
    with open(os.path.join(directory, f"step_{step:010d}",
                           "manifest.json")) as f:
        return json.load(f)["extra"]
