"""Kernels: device time per traced step of the ops around the flash
kernels whose innermost phase is ``attn.relayout`` (ms): the head-major
transposes and row padding of q/k/v/dO, the lse/delta columns, and the
backward's ``delta`` rowsum."""
from metrics._per_step import phase_ms


def read(obs):
    return phase_ms(obs, "attn.relayout")
