"""`correct` at a size a test run can hold, on the CPU: a sound run passes;
the control (the reference in bfloat16 in the program's place) and each
fault planted under the timed path (bench/faults.py) fail. The cells'
own limits are used as they stand."""
import time

import pytest

from bench import common, faults, run

SEED = 2**31 + 77


def small(cell_name: str):
    """The cell at 256 rows a batch and a table of about 2^16 rows."""
    cell, bench = run.cell_spec(cell_name)
    config = common.load_json("configs", f"{cell['config']}.json")
    traffic = common.load_json("traffic", f"{cell['traffic']}.json")
    corpus = config["corpus"]
    if "cat_cardinalities" in corpus:
        corpus["cat_cardinalities"] = [min(c, 2048)
                                       for c in corpus["cat_cardinalities"]]
        f = int(common.load_module("traffic", corpus["generator"])
                .cardinalities(corpus).sum())
    else:
        f = 1 << 16
    config["model"]["num_features"] = corpus["num_features"] = f
    traffic.update(global_batch=256, pool_batches=8)
    return cell, bench, config, traffic


def one_run(cell_name: str, fault: str | None = None) -> dict:
    cell, bench, config, traffic = small(cell_name)

    def go():
        return run.run_cell(cell, bench, SEED, 1.0, False,
                            setup_start=time.perf_counter(), config=config,
                            traffic=traffic)

    if fault is None:
        return go()
    with faults.planted(fault):
        return go()


TRAIN = ["train.zipf27", "train.criteo-synth"]


@pytest.mark.parametrize("cell", TRAIN)
def test_sound_run_is_correct(cell):
    line = one_run(cell)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("cell,fault", [
    (c, f) for c in TRAIN for f in faults.FAULTS])
def test_fault_is_caught(cell, fault):
    line = one_run(cell, fault)
    assert not line["correct"], line["checks"]


def _train_driver():
    return common.load_module("drivers", "train")


@pytest.mark.parametrize("cell", TRAIN)
def test_training_control_is_not_correct(cell):
    _, _, config, traffic = small(cell)
    drv = _train_driver()
    pool = drv.make_pool(config, traffic, SEED)[:4]
    ref = drv.reference_readings(config["model"], pool[:3], pool)
    low = drv.reference_readings(config["model"], pool[:3], pool,
                                 "bfloat16")
    limits = common.load_json("limits", f"{cell}.json")
    found = drv.gaps(low, ref)
    assert not common.judge({k: (v, limits[k]) for k, v in found.items()})

