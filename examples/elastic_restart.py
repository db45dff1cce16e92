"""Fault-tolerance + elastic-rescaling walkthrough.

1. Train with periodic checkpoints; a failure is injected mid-run.
2. run_with_restarts restores from the last checkpoint and finishes.
3. The final state is then RESHARDED onto a different mesh (elastic
   scale-down/up), and training continues there — the 1000-node recovery
   story in miniature.

    PYTHONPATH=src python examples/elastic_restart.py
"""
import logging
import shutil
import tempfile

import jax

from repro.launch.train import build_parser, train_loop
from repro.runtime.fault_tolerance import FailureInjector, run_with_restarts

logging.basicConfig(level=logging.WARNING)

tmp = tempfile.mkdtemp(prefix="repro_elastic_")
args = build_parser().parse_args([
    "--arch", "granite-8b", "--smoke", "--steps", "30", "--batch", "4",
    "--seq", "32", "--ckpt", tmp, "--save-every", "5", "--log-every", "0"])

inj = FailureInjector(fail_at_steps=[13])
last = run_with_restarts(lambda _:
                         train_loop(args, fail_injector=inj)["last_step"],
                         max_restarts=2)
print(f"phase 1: survived injected failure at step 13, reached step {last}")

# elastic restore: same checkpoint, different (logical) mesh
from repro.ckpt.checkpointer import Checkpointer
from repro.configs.base import ParallelConfig, TrainConfig
from repro.launch.mesh import make_host_mesh
from repro.models import registry
from repro.train import trainer

mesh2 = make_host_mesh(1, 1)   # on real hardware: a different pod shape
cfg = registry.smoke_config("granite-8b")
spec = registry.get_spec("granite-8b")
tc = TrainConfig()
pc = ParallelConfig()
with jax.set_mesh(mesh2):
    like = trainer.init_state(spec, cfg, tc, pc, jax.random.PRNGKey(0))
    sdefs = trainer.state_defs(spec, cfg, tc, pc)
    shardings = trainer.shardings_for_state(sdefs, mesh2)
    restored, manifest = Checkpointer(tmp).restore(like, shardings=shardings)
print(f"phase 2: restored step-{manifest['step']} checkpoint under the new "
      f"mesh shardings (elastic reshard)")
shutil.rmtree(tmp)
print("OK")
