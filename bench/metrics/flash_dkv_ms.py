"""Kernels: device time per traced step of the dK/dV kernel of the flash
backward (ms), the trace's ``terapipe_attention_bwd-dkv`` events (the program's
``pallas_call(name=...)``)."""
from metrics._per_step import kernel_ms

PATTERN = r"^terapipe_attention_bwd-dkv\b"


def read(obs):
    return kernel_ms(obs, PATTERN)
