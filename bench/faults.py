"""Faults planted under a training cell's timed path, for the tests and the
chip readings that set the limits.  Each wraps the program's un-jitted
``train_step(params, opt_state, batch)``; nothing of the program is edited.

* :func:`state_unchanged` — the step returns its state as it came in;
* :func:`half_batch` — the second half of the batch's rows is replaced by
  the first, so the mean is taken over half the batch;
* :func:`drop_exchange` — the pipeline's ring (``jax.lax.ppermute``) hands
  each device its own value instead of its neighbour's.
"""
from __future__ import annotations

import contextlib

import jax


def state_unchanged(step):
    def run(params, opt_state, batch):
        _, _, loss = step(params, opt_state, batch)
        return params, opt_state, loss
    return run


def half_batch(step):
    def run(params, opt_state, batch):
        half = {k: v.at[v.shape[0] // 2:].set(v[:v.shape[0] - v.shape[0] // 2])
                for k, v in batch.items()}
        return step(params, opt_state, half)
    return run


@contextlib.contextmanager
def _no_ppermute():
    orig = jax.lax.ppermute
    jax.lax.ppermute = lambda x, axis_name, perm, **_kw: x
    try:
        yield
    finally:
        jax.lax.ppermute = orig


def drop_exchange(step):
    def run(params, opt_state, batch):
        with _no_ppermute():
            return step(params, opt_state, batch)
    return run


HOOKS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
         "drop_exchange": drop_exchange}
