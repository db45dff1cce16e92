"""Checkpoint manager + fault tolerance: atomicity, keep-N, resume
determinism, failure-injected restart, elastic restore."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpointer import Checkpointer
from repro.launch.train import build_parser, train_loop
from repro.runtime.fault_tolerance import (FailureInjector, PreemptionGuard,
                                           StragglerWatchdog,
                                           run_with_restarts)


def _state(x=1.0):
    return {"a": jnp.full((4, 4), x), "b": {"c": jnp.arange(3)},
            "step": jnp.int32(0)}


def test_save_restore_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    s = _state(3.5)
    ck.save(10, s, extra={"data_step": 10})
    restored, manifest = ck.restore(_state(0.0))
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(s["a"]))
    assert manifest["extra"]["data_step"] == 10


def test_keep_n_retention(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    for step in (1, 2, 3, 4):
        ck.save(step, _state(step))
    assert ck.all_steps() == [3, 4]


def test_async_save(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=3)
    ck.save(5, _state(5.0), block=False)
    ck.wait()
    assert ck.latest_step() == 5


@pytest.mark.parametrize("surface", ["wait", "next_save"])
def test_async_save_failure_raises(tmp_path, monkeypatch, surface):
    """A background write that fails is re-raised from `wait()` and from
    the next `save()`, never lost on its thread."""
    from repro.ckpt import checkpointer

    ck = Checkpointer(str(tmp_path), keep=3)

    def broken_save(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr(checkpointer.np, "save", broken_save)
    ck.save(5, _state(5.0), block=False)
    with pytest.raises(RuntimeError, match="async checkpoint") as info:
        if surface == "wait":
            ck.wait()
        else:
            ck.save(6, _state(6.0), block=False)
    assert isinstance(info.value.__cause__, OSError)
    assert ck.latest_step() is None
    monkeypatch.undo()
    ck.wait()                       # the error was reported once
    ck.save(7, _state(7.0), block=False)
    ck.wait()
    assert ck.latest_step() == 7


def test_atomic_no_partial_dirs(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=3)
    ck.save(1, _state())
    for name in os.listdir(tmp_path):
        assert not name.endswith(".tmp")


def test_restore_missing_raises(tmp_path):
    ck = Checkpointer(str(tmp_path))
    with pytest.raises(FileNotFoundError):
        ck.restore(_state())


def test_truncated_manifest_falls_back_to_previous(tmp_path):
    """The crash-consistency contract: a checkpoint whose manifest was cut
    off mid-write (simulated partial write/crash) is INVISIBLE — discovery
    skips it and restore hands back the newest complete step instead of
    crashing on the bad one."""
    ck = Checkpointer(str(tmp_path), keep=5)
    ck.save(1, _state(1.0))
    ck.save(2, _state(2.0))
    manifest = tmp_path / "step_0000000002" / "manifest.json"
    raw = manifest.read_bytes()
    manifest.write_bytes(raw[: len(raw) // 2])       # truncate mid-write
    assert ck.all_steps() == [1]
    assert ck.latest_step() == 1
    restored, man = ck.restore(_state(0.0))
    assert man["step"] == 1
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(_state(1.0)["a"]))
    # a missing manifest (killed before the in-dir rename) hides the same way
    manifest.unlink()
    assert ck.all_steps() == [1]


def test_async_save_bit_exact_vs_sync(tmp_path):
    """`block=False` must produce byte-identical array files and an
    equivalent manifest to the synchronous path at the same step."""
    s = _state(7.25)
    sync, asyn = Checkpointer(str(tmp_path / "s")), \
        Checkpointer(str(tmp_path / "a"))
    sync.save(3, s, extra={"k": 1}, block=True)
    asyn.save(3, s, extra={"k": 1}, block=False)
    asyn.wait()
    d_s, d_a = (tmp_path / m / "step_0000000003" for m in ("s", "a"))
    names = sorted(p.name for p in d_s.iterdir())
    assert names == sorted(p.name for p in d_a.iterdir())
    for name in names:
        if name == "manifest.json":
            import json

            ms = json.loads((d_s / name).read_text())
            ma = json.loads((d_a / name).read_text())
            ms.pop("time"), ma.pop("time")
            assert ms == ma
        else:
            assert (d_s / name).read_bytes() == (d_a / name).read_bytes()


def test_async_snapshot_isolation_under_donation(tmp_path):
    """An async save captures the PRE-step state even though the training
    loop immediately keeps going and the jitted step DONATES (mutates in
    place) the very buffers that were live at save time — the device->host
    snapshot happens inside save(), before it returns."""
    from repro.api import DPMREngine
    from repro.configs.base import DPMRConfig
    from repro.data import get_source
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(1, 1)
    cfg = DPMRConfig(num_features=1 << 10, max_features_per_sample=8)
    src = get_source("zipf_sparse", batch_size=32, num_batches=16,
                     num_features=1 << 10, features_per_sample=8, seed=0)
    eng = DPMREngine(cfg, mesh)
    eng.fit_sgd(src, steps=2)
    snap = np.asarray(eng.state.cold).copy()
    step_saved = eng.save(str(tmp_path), block=False)
    eng.fit_sgd(src, steps=3)               # donates/overwrites live state
    eng.wait_saves()
    fresh = DPMREngine(cfg, make_host_mesh(1, 1))
    manifest = fresh.restore(str(tmp_path))
    assert manifest["step"] == step_saved == 2
    np.testing.assert_array_equal(np.asarray(fresh.state.cold), snap)
    assert not np.array_equal(np.asarray(eng.state.cold), snap)


def _args(tmp, steps, save_every=5):
    return build_parser().parse_args([
        "--arch", "yi-6b", "--smoke", "--steps", str(steps), "--batch", "4",
        "--seq", "16", "--ckpt", str(tmp), "--save-every", str(save_every),
        "--log-every", "0"])


def test_resume_is_deterministic(tmp_path):
    """Straight 16-step run == 8 steps + crash + resume (same final loss)."""
    a = str(tmp_path / "a")
    out1 = train_loop(_args(a, 16, save_every=100))

    b = str(tmp_path / "b")
    args_b = _args(b, 8, save_every=8)
    train_loop(args_b)
    args_b2 = _args(b, 16, save_every=100)
    out2 = train_loop(args_b2)
    np.testing.assert_allclose(out1["losses"][-1], out2["losses"][-1],
                               rtol=1e-4)


def test_injected_failure_recovery(tmp_path):
    inj = FailureInjector(fail_at_steps=[6])
    args = _args(str(tmp_path), 12, save_every=3)

    def loop(_):
        return train_loop(args, fail_injector=inj)["last_step"]

    last = run_with_restarts(loop, max_restarts=2)
    assert last == 12
    assert inj.failed == [6]


def test_preemption_guard_triggers_save(tmp_path):
    guard = PreemptionGuard(signals=())
    guard.trigger()
    assert guard.preempted()


def test_straggler_watchdog_flags_outlier():
    import time

    wd = StragglerWatchdog(window=10, factor=2.0)
    for i in range(6):
        wd.step_start()
        time.sleep(0.01)
        wd.step_end(i)
    wd.step_start()
    time.sleep(0.15)
    wd.step_end(99)
    assert wd.events and wd.events[-1]["step"] == 99


def test_elastic_restore_under_new_sharding(tmp_path):
    """Save replicated, restore sharded (mesh change) — values identical."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_host_mesh

    ck = Checkpointer(str(tmp_path))
    s = {"w": jnp.arange(16.0).reshape(4, 4)}
    ck.save(1, s)
    mesh = make_host_mesh(1, 1)
    sh = {"w": NamedSharding(mesh, P("data", None))}
    restored, _ = ck.restore(s, shardings=sh)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(s["w"]))
    assert restored["w"].sharding == sh["w"]
