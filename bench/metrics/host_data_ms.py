"""Entry: host time per step to make the batch (``DataPipeline.batch_at``)
and hand it to the device (``jax.device_put``), mean over the traced
steps, in ms (host clock)."""


def read(obs):
    if not obs.data_s:
        return None
    return 1e3 * sum(obs.data_s) / len(obs.data_s)
