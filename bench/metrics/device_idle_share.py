"""Device: 1 - (union of op intervals) / traced window, mean over the
cell's devices (%), from the device trace."""
import trace


def read(obs):
    busy = trace.device_busy_s(obs.trace, obs.wins)
    if not busy or obs.window_s <= 0:
        return None
    return 100.0 * (1 - sum(busy.values()) / len(busy) / obs.window_s)
