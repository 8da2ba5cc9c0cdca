"""Executor: per pipeline device, 1 - busy / traced window from the device
trace; the largest over the devices (%)."""
import trace


def read(obs):
    busy = trace.device_busy_s(obs.trace, obs.wins)
    if len(busy) < 2 or obs.window_s <= 0:
        return None
    return 100.0 * (1 - min(busy.values()) / obs.window_s)
