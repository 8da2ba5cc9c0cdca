"""Device: peak bytes in use plus peak bytes reserved (where the TPU
runtime keeps a program's temporaries), the largest over devices (GiB),
from ``memory_stats()`` after the window."""


def read(obs):
    return max(obs.memory) / 2 ** 30 if obs.memory and max(obs.memory) else None
