"""The DPMR exchange across four devices, at the shapes of the four-chip
benchmark configuration (`bench/configs/dpmr-zipf-1p6b.json`: its model
keys as they stand, F cut to 3 * 2^14), on four emulated CPU devices in a
subprocess (XLA_FLAGS cannot change once jax has started here).

At P = 4 every sample asks the owner of each of its ids for the parameter
and sends the gradient back to it: three steps of `DPMREngine` must agree
with the routing-free `repro.core.reference`, and every all-to-all of the
compiled step must sit under the `exchange` scope, both in its op metadata
and in `bench/program_trace.py`'s reading of it.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BODY = """
import json
import sys

import numpy as np

from bench import common, program_trace
from repro.api import DPMREngine, hot_ids_from_corpus
from repro.core import dpmr, reference
from repro.launch.mesh import make_host_mesh

config = common.load_json("configs", "dpmr-zipf-1p6b.json")
f = 3 * 2**14
config["model"]["num_features"] = config["corpus"]["num_features"] = f
cfg = common.model_config(config)
gen = common.load_module("traffic", config["corpus"]["generator"])
batches = [gen.make_batch(config["corpus"], 1024, 2**31 + 5 + i)
           for i in range(4)]
mesh = make_host_mesh(1, 4)
engine = DPMREngine(cfg, mesh, hot_ids=hot_ids_from_corpus(cfg, batches,
                                                           mesh))
hist = [engine.train_step(b) for b in batches[:3]]
theta, _, losses = reference.sgd_steps(cfg, batches[:3],
                                       dpmr.padded_features(cfg, mesh))
np.save(sys.argv[1], np.stack([reference.engine_table(engine.state),
                               np.asarray(theta)]))
text = engine.step_fns(1024).train_step.lower(
    engine.state, engine.put_batch(batches[3])).compile().as_text()
a2a = [l.strip().removeprefix("ROOT ") for l in text.splitlines()
       if " all-to-all(" in l]
_, sub_of = program_trace.instruction_scopes([text])
op_names = [program_trace._OP_NAME.search(l) for l in a2a]
print(json.dumps({
    "shards": engine.fns.num_shards, "capacity": engine.fns.capacity,
    "losses": [h["loss"] for h in hist], "ref_losses": losses,
    "overflow": [h["overflow"] for h in hist],
    "a2a_op_names": [m.group(1) if m else "" for m in op_names],
    "a2a_substages": [sub_of.get(program_trace.trace_reduce.parse(l)[0])
                      for l in a2a]}))
"""


@pytest.fixture(scope="module")
def four_devices(tmp_path_factory):
    tables = str(tmp_path_factory.mktemp("x4") / "tables.npy")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([REPO, os.path.join(REPO, "src")]))
    proc = subprocess.run([sys.executable, "-c", BODY, tables], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    out["tables"] = np.load(tables)
    return out


def test_three_adagrad_steps_match_reference(four_devices):
    out = four_devices
    assert out["shards"] == 4
    # 1024 rows x 64 slots over 4 devices, 4x the uniform mean per owner
    assert out["capacity"] == 256 * 64
    assert out["overflow"] == [0, 0, 0]
    # float32 summation order differs between the routed sums and the
    # reference's dense scatter-add; nothing else may differ
    np.testing.assert_allclose(out["losses"], out["ref_losses"], rtol=1e-5)
    table, theta = out["tables"]
    assert np.count_nonzero(theta) > 0
    np.testing.assert_allclose(table, theta, rtol=1e-5, atol=1e-6)


def test_every_all_to_all_is_named_exchange(four_devices):
    names = four_devices["a2a_op_names"]
    # requests and responses in distribute, gradient sums in reduce
    assert len(names) >= 3
    assert all("/exchange/" in n for n in names), names


def test_program_trace_puts_all_to_alls_under_exchange(four_devices):
    subs = four_devices["a2a_substages"]
    assert len(subs) >= 3
    assert set(subs) == {"exchange"}, subs
