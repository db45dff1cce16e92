"""Faults of the exchange between chips, planted as bench/faults.py plants
its own (same `FAULTS` and `planted`), to show that `correct` catches a
program whose chips stop talking to each other. They change nothing on a
one-chip mesh, where every owner is the chip itself:

  exchange_local    every `lax.all_to_all` keeps what it would send: each
                    chip's requests, responses and gradient sums stay on
                    the chip, and its owner side reads them as if they
                    had come from the other chips

The patch holds while the step is traced, so plant it before the engine
that runs the step is built.
"""
from __future__ import annotations

import contextlib

FAULTS = ("exchange_local",)


@contextlib.contextmanager
def planted(name: str):
    import jax

    if name not in FAULTS:
        raise ValueError(f"fault must be one of {FAULTS}: {name!r}")
    original = jax.lax.all_to_all

    def exchange_local(x, axis_name, split_axis, concat_axis, *,
                       tiled=False, **kw):
        if tiled and split_axis == concat_axis:
            return x
        return original(x, axis_name, split_axis, concat_axis, tiled=tiled,
                        **kw)

    jax.lax.all_to_all = exchange_local
    try:
        yield
    finally:
        jax.lax.all_to_all = original
