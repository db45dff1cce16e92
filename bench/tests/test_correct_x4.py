"""`correct` for the four-chip cell `train.zipf1p6b.x4`, at a size a test
run can hold: F = 3 * 2^14, 1024 rows a batch, 8 pool batches, on four
emulated CPU devices in a subprocess (XLA_FLAGS cannot change once jax
has started here). A sound run passes; the control (the reference in
bfloat16 in the program's place), each fault planted under the timed
path (bench/faults.py) and each fault of the exchange between chips
(bench/exchange_faults.py) fail. The cell's own limits are used as they
stand."""
import json
import os
import subprocess
import sys

import pytest

from bench import exchange_faults, faults

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "train.zipf1p6b.x4"
SEED = 2**31 + 77

BODY = f"""
import json
import time

from bench import common, exchange_faults, faults, run

cell, bench = run.cell_spec({CELL!r})
config = common.load_json("configs", f"{{cell['config']}}.json")
traffic = common.load_json("traffic", f"{{cell['traffic']}}.json")
config["model"]["num_features"] = config["corpus"]["num_features"] = \\
    3 * 2**14
traffic.update(global_batch=1024, pool_batches=8)


def go():
    return run.run_cell(cell, bench, {SEED}, 1.0, False,
                        setup_start=time.perf_counter(), config=config,
                        traffic=traffic)


out = {{"sound": go()}}
for kind in (faults, exchange_faults):
    for name in kind.FAULTS:
        with kind.planted(name):
            out[name] = go()
drv = common.load_module("drivers", "train")
pool = drv.make_pool(config, traffic, {SEED})[:4]
ref = drv.reference_readings(config["model"], pool[:3], pool)
low = drv.reference_readings(config["model"], pool[:3], pool, "bfloat16")
limits = common.load_json("limits", f"{{cell['name']}}.json")
out["control"] = common.judge(
    {{k: (v, limits[k]) for k, v in drv.gaps(low, ref).items()}})
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", BODY], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(runs):
    line = runs["sound"]
    assert line["correct"], line["checks"]
    assert line["device"]["count"] == 4
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", faults.FAULTS + exchange_faults.FAULTS)
def test_fault_is_caught(runs, fault):
    assert not runs[fault]["correct"], runs[fault]["checks"]


def test_training_control_is_not_correct(runs):
    assert runs["control"] is False
