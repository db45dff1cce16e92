"""Faults planted under the measured path, to show that `correct` catches
them. Each patches the program's entry point that the window drives, for
as long as the context is open:

  state_unchanged   a training step that returns the state it was given
                    (the step runs on a copy, and its loss is reported)
  half_batch        a training step that sees only the first half of its
                    batch, the mean taken over that half (the first half
                    is sent twice)
"""
from __future__ import annotations

import contextlib

FAULTS = ("state_unchanged", "half_batch")


@contextlib.contextmanager
def planted(name: str):
    import jax
    import jax.numpy as jnp

    from repro.api import DPMREngine

    if name not in FAULTS:
        raise ValueError(f"fault must be one of {FAULTS}: {name!r}")
    original = DPMREngine.train_step

    def state_unchanged(self, batch):
        keep = self.state
        self.state = jax.tree.map(jnp.copy, keep)
        m = original(self, batch)
        self.state = keep
        return m

    def half_batch(self, batch):
        def first_half_twice(x):
            h = x[:x.shape[0] // 2]
            return jnp.concatenate([h, h])

        return original(self, {k: first_half_twice(v)
                               for k, v in batch.items()})

    DPMREngine.train_step = locals()[name]
    try:
        yield
    finally:
        DPMREngine.train_step = original
