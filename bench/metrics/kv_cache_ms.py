"""Executor: device time per traced step of the ops whose innermost phase
is ``tp.cache`` (ms): KV-cache writes and reads, the fresh-microbatch
cache zeroing, the per-chunk cache merge and the explicit-backward
residual saves and reads."""
from metrics._per_step import phase_ms


def read(obs):
    return phase_ms(obs, "tp.cache")
