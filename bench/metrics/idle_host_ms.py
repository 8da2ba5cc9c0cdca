"""Entry: device idle time per traced step while the host is inside the
program's ``data.batch_at`` span or the benchmark's ``dispatch`` span (ms):
the device waiting on the host to make a batch or to launch a step.  The
host spans are moved onto each device's clock as ``trace.device_windows``
moves the window; summed over devices, over devices and traced steps."""
import trace

SPANS = ("data.batch_at", "dispatch")


def read(obs):
    host = [o for o in obs.trace.host if o.name in SPANS]
    if not host or not obs.trace.devices or not obs.steps:
        return None
    host_lo = trace.window(obs.trace)[0]
    total = 0.0
    for dev, ops in obs.trace.devices.items():
        win = obs.wins[dev]
        skew = host_lo - win[0]
        idle = trace.subtract([win], trace.busy(ops, win))
        spans = trace.union(trace.clip(
            ((h.start - skew, h.end - skew) for h in host), *win))
        total += trace.length(idle) - trace.length(trace.subtract(idle, spans))
    return total / len(obs.trace.devices) / obs.steps / 1e6
