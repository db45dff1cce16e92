#!/usr/bin/env python
"""Real-multi-process parity gate: a 2-process `jax.distributed` CPU run
of launch/train.py must bit-match the single-process all-hosts emulation.

Drives three things and diffs their JSON summaries:

  1. baseline: one process, 4 emulated devices,
     `--hosts 2 --host-id -1` (the concatenated global-batch emulation);
  2. the real thing: two coordinated processes (2 local devices each,
     same 4-device global mesh), `--coordinator/--num-processes/
     --process-id`, each serving its own host's stride of the corpus;
  3. the parity assertions:
       - `cold_md5` (the gathered final parameter table) identical — the
         bit-identity claim;
       - `final_eval_loss` (host-side float64 eval on a fixed batch)
         identical — bit-identical loss, computed deterministically;
       - per-step training losses equal to ~1 ulp (the `pmean` metric may
         legitimately differ in reduction order across process
         boundaries — that is why the two exact checks above exist);
       - both processes of the real run report the same digest.

Run locally (takes ~2 min on CPU):  python scripts/check_multiprocess.py
Nightly CI runs it after the slow suite (.github/workflows/ci.yml);
tests/test_multiprocess.py wraps it so `pytest -m slow` covers it too.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PORT = int(os.environ.get("REPRO_MP_PORT", "12741"))

COMMON = ["--sparse", "--strategy", "a2a", "--features", "1024",
          "--batch", "32", "--sparse-batches", "64", "--steps", "6",
          "--mesh-data", "4", "--prefetch", "0", "--save-every", "100",
          "--json", "--log-every", "0"]


def _run(extra: list[str], timeout: int = 600) -> subprocess.Popen:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)       # --local-devices owns the device count
    # a CPU emulation of multi-host: the children never reach for a chip
    # (on a TPU machine two of them would each try to take every chip)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(
        [sys.executable, "-m", "repro.launch.train", *COMMON, *extra],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def _summary(proc: subprocess.Popen, timeout: int = 600) -> dict:
    out, err = proc.communicate(timeout=timeout)
    if proc.returncode != 0:
        sys.exit(f"train.py exited {proc.returncode}:\n{err[-4000:]}")
    return json.loads(out.strip().splitlines()[-1])


def main() -> int:
    print("== baseline: single-process all-hosts emulation "
          "(--hosts 2 --host-id -1, 4 devices) ==")
    base = _summary(_run(["--hosts", "2", "--host-id", "-1",
                          "--local-devices", "4"]))

    print(f"== real run: 2 coordinated processes, 2 local devices each "
          f"(coordinator 127.0.0.1:{PORT}) ==")
    mp = ["--coordinator", f"127.0.0.1:{PORT}",
          "--num-processes", "2", "--local-devices", "2"]
    p1 = _run([*mp, "--process-id", "1"])
    p0 = _run([*mp, "--process-id", "0"])
    s0, s1 = _summary(p0), _summary(p1)

    failures = []
    if s0["cold_md5"] != s1["cold_md5"]:
        failures.append(f"the two processes disagree on the final "
                        f"parameters: {s0['cold_md5']} vs {s1['cold_md5']}")
    if base["cold_md5"] != s0["cold_md5"]:
        failures.append(
            f"final parameters diverge from the emulated baseline: "
            f"emulated {base['cold_md5']} vs real {s0['cold_md5']}")
    if base["final_eval_loss"] != s0["final_eval_loss"]:
        failures.append(
            f"deterministic final eval loss diverges: emulated "
            f"{base['final_eval_loss']!r} vs real {s0['final_eval_loss']!r}")
    for i, (a, b) in enumerate(zip(base["losses"], s0["losses"],
                                   strict=True)):
        if abs(a - b) > 1e-6:
            failures.append(f"step {i} loss diverges beyond metric "
                            f"tolerance: {a!r} vs {b!r}")

    print(f"emulated : eval_loss={base['final_eval_loss']!r} "
          f"cold_md5={base['cold_md5']}")
    print(f"2-process: eval_loss={s0['final_eval_loss']!r} "
          f"cold_md5={s0['cold_md5']}")
    for f in failures:
        print(f"PARITY FAILURE: {f}", file=sys.stderr)
    if not failures:
        print("multiprocess parity OK: bit-identical final parameters + "
              "deterministic eval loss, per-step metric within 1e-6")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
