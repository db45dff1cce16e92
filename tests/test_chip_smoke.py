"""`chip_smoke.py` on the CPU: it refuses to run without a TPU, and its
phases pass at a small size.

The phases are rehearsed by shrinking the script's own size constants and
by mapping its `"pallas"` kernels to `"pallas_interpret"` (Mosaic compiles
only for a TPU); everything else is the path the chip runs.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")

# the shrink, applied in this process and in the four-device child
SHRINK = """
import chip_smoke
_make_cfg = chip_smoke.make_cfg
chip_smoke.make_cfg = lambda kernel_impl="xla": _make_cfg(
    "pallas_interpret" if kernel_impl == "pallas" else kernel_impl)
chip_smoke.LOG2_FEATURES, chip_smoke.BATCH, chip_smoke.STEPS = 18, 256, 4
chip_smoke.REQUESTS, chip_smoke.HOT_REQUESTS = 8, 24
"""


def _env(**kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(kw)
    return env


def test_refuses_cpu():
    """No TPU: exit 1 before any phase, name the platform, print no
    result line."""
    out = subprocess.run([sys.executable, SMOKE], capture_output=True,
                         text=True, env=_env(), timeout=300, cwd=ROOT)
    assert out.returncode == 1, out
    assert "platform is 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout
    assert "[train" not in out.stdout


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, "chip_smoke", mod)
    spec.loader.exec_module(mod)
    exec(SHRINK, {})
    return mod


def test_one_chip_phases(smoke, capsys):
    smoke.one_chip(seed=0)
    out = capsys.readouterr().out.splitlines()
    checks = [l for l in out if l.endswith(("PASS", "FAIL"))]
    assert [l.split("]")[0] for l in checks] == [
        "[reference", "[pallas vs xla", "[serve"], checks
    assert all(l.endswith("PASS") for l in checks), checks
    hits = int(checks[-1].split("cache hits ")[1].split(",")[0])
    assert hits > 0, checks[-1]


def test_four_chips_phases():
    """The `--chips 4` pair on four CPU devices: the a2a exchange at P = 4
    against one device and the reference."""
    code = (f"import sys; sys.path.insert(0, {ROOT!r})\n" + SHRINK
            + "chip_smoke.four_chips(seed=0)\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=ROOT,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, out.stderr[-4000:]
    lines = [l for l in out.stdout.splitlines() if " vs " in l]
    assert len(lines) == 3 and all(l.endswith("PASS") for l in lines), lines
    assert "[train 4 chips] P=4" in out.stdout
