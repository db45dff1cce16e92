"""GPipe pipeline parallelism: schedule correctness + gradient flow."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str, devices: int = 4, timeout: int = 600) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", body], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_pipeline_matches_sequential():
    out = run_py("""
import json
import jax, jax.numpy as jnp, numpy as np
from repro.train.pipeline import make_pp_mesh, pipeline_apply

S, M, B, D = 4, 8, 2, 16
mesh = make_pp_mesh(S)
rng = np.random.default_rng(0)
w = jnp.asarray(rng.normal(0, 0.3, size=(S, D, D)), jnp.float32)
x = jnp.asarray(rng.normal(size=(M, B, D)), jnp.float32)

def stage_fn(w_s, h):
    return jnp.tanh(h @ w_s)

with jax.set_mesh(mesh):
    y_pipe = pipeline_apply({"w": w}, x,
                            lambda p, h: stage_fn(p["w"], h), mesh)

# sequential oracle
y_ref = x
for s in range(S):
    y_ref = jnp.tanh(y_ref @ w[s])
diff = float(jnp.max(jnp.abs(y_pipe - y_ref)))

# gradient through the pipeline
def loss(w):
    y = pipeline_apply({"w": w}, x, lambda p, h: stage_fn(p["w"], h), mesh)
    return jnp.sum(jnp.sin(y))

def loss_ref(w):
    y = x
    for s in range(S):
        y = jnp.tanh(y @ w[s])
    return jnp.sum(jnp.sin(y))

with jax.set_mesh(mesh):
    g_pipe = jax.grad(loss)(w)
g_ref = jax.grad(loss_ref)(w)
gdiff = float(jnp.max(jnp.abs(g_pipe - g_ref)))
print(json.dumps({"fwd": diff, "bwd": gdiff}))
""")
    assert out["fwd"] < 1e-5, out
    assert out["bwd"] < 1e-5, out


def test_bubble_fraction():
    from repro.train.pipeline import bubble_fraction

    assert bubble_fraction(4, 8) == 3 / 11
    assert bubble_fraction(1, 8) == 0.0
    assert bubble_fraction(8, 32) < 0.2
