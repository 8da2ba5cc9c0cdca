"""Ring communication: per step, collective-permute time during which no
other op runs on that device; the largest over devices (ms), from the
device trace."""
import re

import trace

PERMUTE = re.compile(r"collective-permute", re.I)


def read(obs):
    if len(obs.trace.devices) < 2 or not obs.steps:
        return None
    found = any(PERMUTE.search(o.name) for ops in obs.trace.devices.values()
                for o in ops)
    if not found:
        return None
    worst = max(trace.exposed_collective(ops, obs.wins[dev], PERMUTE)
                for dev, ops in obs.trace.devices.items())
    return worst / obs.steps / 1e6
