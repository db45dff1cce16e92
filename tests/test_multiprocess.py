"""Real multi-process execution (tentpole of the distributed runtime):
two OS processes, one `jax.distributed` coordinator, one global mesh —
parity with the single-process emulation, and async-checkpoint restore
across an actual kill + relaunch at a different host count.

Everything runs in subprocesses: the pytest process itself must never
initialize jax.distributed (XLA_FLAGS and the coordinator are per-process,
one-shot). Marked slow like the other subprocess suites.
"""
import json
import os
import signal
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)      # --local-devices owns the device count
    env["JAX_PLATFORMS"] = "cpu"    # a CPU emulation of multi-host
    return env


def _train(extra, steps, save_every=100, ckpt="", async_ckpt=False):
    args = [sys.executable, "-m", "repro.launch.train", "--sparse",
            "--strategy", "a2a", "--features", "1024", "--batch", "32",
            "--sparse-batches", "64", "--mesh-data", "4", "--prefetch", "0",
            "--json", "--log-every", "0", "--steps", str(steps),
            "--save-every", str(save_every)]
    if ckpt:
        args += ["--ckpt", ckpt]
    if async_ckpt:
        args += ["--async-ckpt"]
    return subprocess.Popen(args + extra, env=_env(),
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def _summary(proc, timeout=600):
    out, err = proc.communicate(timeout=timeout)
    assert proc.returncode == 0, err[-4000:]
    return json.loads(out.strip().splitlines()[-1])


def test_two_process_parity_gate():
    """The exact gate nightly CI runs: a real 2-process coordinated run
    bit-matches the `--hosts 2 --host-id -1` emulation (final parameter
    digest + deterministic float64 eval loss; step metrics within 1 ulp
    tolerance). scripts/check_multiprocess.py owns the comparison."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "check_multiprocess.py")],
        env={**_env(), "REPRO_MP_PORT": "12747"},
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_async_ckpt_survives_kill_and_elastic_restart(tmp_path):
    """Kill a live 2-process run mid-training; the async-written
    checkpoint restores into a SINGLE-process relaunch (new data-plane
    host count, same global mesh) which resumes and finishes — the
    paper's restartable outer loop over real process boundaries."""
    ckpt = str(tmp_path / "ck")
    mp = ["--coordinator", "127.0.0.1:12749", "--num-processes", "2",
          "--local-devices", "2"]
    # far more steps than run before the kill: a warm compile cache makes
    # steps fast, and the kill must land mid-run whatever the speed
    p1 = _train([*mp, "--process-id", "1"], steps=100000, save_every=2,
                ckpt=ckpt, async_ckpt=True)
    p0 = _train([*mp, "--process-id", "0"], steps=100000, save_every=2,
                ckpt=ckpt, async_ckpt=True)
    try:
        # wait for at least one COMPLETE checkpoint (manifest present)
        deadline = time.time() + 300
        while time.time() < deadline:
            steps = [d for d in (os.listdir(ckpt) if os.path.isdir(ckpt)
                                 else [])
                     if d.startswith("step_") and not d.endswith(".tmp")
                     and os.path.exists(os.path.join(ckpt, d,
                                                     "manifest.json"))]
            if steps:
                break
            if p0.poll() is not None and p1.poll() is not None:
                pytest.fail("run exited before writing a checkpoint: "
                            + p0.communicate()[1][-2000:])
            time.sleep(0.5)
        else:
            pytest.fail("no checkpoint appeared within the deadline")
        # kill one process, then the other — the cluster is gone
        p1.send_signal(signal.SIGKILL)
        p0.send_signal(signal.SIGKILL)
    finally:
        for p in (p0, p1):
            if p.poll() is None:
                p.kill()
            p.communicate()

    # relaunch at H=1 (4 local devices, same 4-device global mesh): the
    # cursor was recorded under num_hosts=2, so restore reassigns
    # ownership (reshard_data_state semantics) and training continues
    # from the newest complete checkpoint for 4 more steps
    saved = max(int(d[5:]) for d in os.listdir(ckpt)
                if d.startswith("step_") and not d.endswith(".tmp")
                and os.path.exists(os.path.join(ckpt, d, "manifest.json")))
    resumed = _summary(_train(["--local-devices", "4"], steps=saved + 4,
                              save_every=4, ckpt=ckpt))
    assert resumed["last_step"] == saved + 4
    assert len(resumed["losses"]) == 4           # resumed, not restarted
    assert resumed["hosts"] == 1 and resumed["num_processes"] == 1


def test_all_hosts_emulation_equals_stride_union():
    """`--host-id -1` serves exactly the concatenation of every host's
    stride batches (pure data-plane check, no jax needed)."""
    import numpy as np

    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.data import get_source
    from repro.runtime.multiprocess import emulate_all_hosts

    src = get_source("zipf_sparse", batch_size=8, num_batches=12,
                     num_features=1 << 10, features_per_sample=8, seed=3)
    wrapped = emulate_all_hosts(src, 3)
    assert wrapped.batch_size == 24 and wrapped.num_batches == 4
    got = wrapped.batch(2)
    want = {k: np.concatenate([np.asarray(src.batch(2 * 3 + h)[k])
                               for h in range(3)])
            for k in got}
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]), want[k])


@pytest.mark.parametrize("extra", [0, 1])
def test_local_device_count_must_take_effect(extra):
    """`local_device_count` is honoured exactly or raises: a count the
    backend did not take (here, one more than the CPU backend has) is an
    error, not a silent run on other devices."""
    import jax

    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.runtime import multiprocess

    n = jax.local_device_count() + extra
    if extra == 0:
        multiprocess._check_local_device_count(n)
    else:
        with pytest.raises(RuntimeError, match="local_device_count"):
            multiprocess._check_local_device_count(n)
