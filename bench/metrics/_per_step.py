"""Shared by the readers of device time per traced step (ms): summed over
the cell's devices, divided by devices and by the traced steps, over the ops
that start inside each device's window."""
from __future__ import annotations

import scopes
import trace


def _per_step(obs, total_ns):
    return total_ns / len(obs.trace.devices) / obs.steps / 1e6


def kernel_ms(obs, pattern: str):
    """Device time of the ops whose name matches ``pattern``; None where
    the trace holds none."""
    if not obs.steps:
        return None
    t = n = 0
    for dev, ops in obs.trace.devices.items():
        dt, dn = trace.op_time(ops, pattern, obs.wins[dev])
        t, n = t + dt, n + dn
    return _per_step(obs, t) if n else None


def phase_ms(obs, phase: str):
    """Device time of the ops whose innermost phase (``scopes.phase_of``
    of their ``op_name``) is ``phase``; None where no op has it."""
    if not obs.trace.devices or not obs.steps:
        return None
    names = scopes.of_run(obs)
    phases = {}
    t, n = 0.0, 0
    for dev, ops in obs.trace.devices.items():
        lo, hi = obs.wins[dev]
        for o in ops:
            if not lo <= o.start < hi:
                continue
            if o.name not in phases:
                phases[o.name] = scopes.phase_of(names.get(o.name))
            if phases[o.name] == phase:
                t, n = t + o.dur, n + 1
    return _per_step(obs, t) if n else None
