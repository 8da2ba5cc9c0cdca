"""Shared by the flash kernels' roofline readers."""
from __future__ import annotations

import flops
import trace


def roofline_share(obs, work, pattern: str, per_call: int = 1):
    """Least time of the work the kernel's calls did over their device time,
    in percent, over all devices.  The work of one call is the mean over a
    pass of the useful work (``flops.flash_pass_work``); calls are the
    trace's events matching ``pattern`` over ``per_call`` (kernels launched
    per call).  None where the trace holds no such kernel."""
    pw = flops.flash_pass_work(obs.config, obs.traffic, obs.slices,
                               obs.n_devices, work)
    if pw is None:
        return None
    f, b, calls = pw
    least_call = max(f / obs.peaks["bf16_flops"],
                     b / obs.peaks["hbm_bytes_per_s"]) / calls
    least = spent = 0.0
    for dev, ops in obs.trace.devices.items():
        t, n = trace.op_time(ops, pattern, obs.wins[dev])
        least += n / per_call * least_call
        spent += t / 1e9
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
