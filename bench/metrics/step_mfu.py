"""Whole step: model FLOPs per token (``flops.model_flops_per_token``:
forward and backward, no recompute, causal attention counted as the half it
needs) times the traced window's tokens per second, over chips times the
chip's bf16 peak (%)."""
import flops


def read(obs):
    if obs.window_s <= 0 or not obs.steps:
        return None
    per_tok = flops.model_flops_per_token(obs.config, obs.traffic["seq"])
    rate = obs.steps * obs.tokens_per_step / obs.window_s
    return 100.0 * per_tok * rate / (obs.n_devices * obs.peaks["bf16_flops"])
