"""Entry: compile events inside the traced window (``jax.monitoring``
backend compiles and persistent-cache loads); should be 0."""


def read(obs):
    return obs.compiles
