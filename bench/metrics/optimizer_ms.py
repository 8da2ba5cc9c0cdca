"""Model step: device time per traced step of the ops whose innermost
phase is ``step.optimizer`` (ms): gradient clipping, the AdamW update and
its application to the parameters."""
from metrics._per_step import phase_ms


def read(obs):
    return phase_ms(obs, "step.optimizer")
