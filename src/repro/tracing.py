"""Names under which the train step shows up in a profile.

A phase is a ``jax.named_scope``: it lands in the ``op_name`` metadata of
every HLO instruction traced inside it (an autodiff backward carries its
forward's scope inside ``transpose(jvp(...))``).  The flash kernels are
named with ``pallas_call(name=...)``, which becomes the name of their
custom call, and so of their event on the device.  The host spans are
``jax.profiler`` annotations.  Scopes and kernel names are compile-time
metadata; the host spans cost nothing unless a profiler is recording.
The counters are host numbers the program records while it builds a step
(:func:`count`), for a reader in the same process (:data:`counters`).
"""

# scopes
OPTIMIZER = "step.optimizer"        # clip + AdamW update, apply_updates
EMBED = "tp.embed"                  # token embedding, pre-pipeline groups
TICK_FWD = "tp.tick.fwd"            # executor switch arms
TICK_BWD = "tp.tick.bwd"
TICK_BWD_INPUT = "tp.tick.bwd_input"
TICK_BWD_WEIGHT = "tp.tick.bwd_weight"
RING = "tp.ring"                    # forward and reverse ppermutes
CACHE = "tp.cache"                  # KV-cache writes, residual save/read
REASSEMBLE = "tp.reassemble"        # last rank's outputs back to (B, L, d)
HEAD_LOSS = "tp.head_loss"          # post groups, final norm, LM head, xent
GRAD_REDUCE = "tp.grad_reduce"      # psums after the tick scan
ATTN_RELAYOUT = "attn.relayout"     # head-major transposes, row padding

# kernels
FLASH_FWD = "terapipe_attention_fwd"
FLASH_BWD_DQ = "terapipe_attention_bwd-dq"
FLASH_BWD_DKV = "terapipe_attention_bwd-dkv"

# host spans
BATCH_AT = "data.batch_at"          # DataPipeline.batch_at
STEP = "train"                      # StepTraceAnnotation, one per step

# counters, recorded when the executor builds its tick loop
TICKS = "tp.ticks"                  # ticks of one pass of the tick loop
RANKS = "tp.ranks"                  # pipeline ranks of the tick table
IDLE_UNITS = "tp.idle_units"        # (tick, rank) units the tick table
                                    # marks idle (fill and drain); under
                                    # fwd-only schedules they still compute

counters: dict = {}


def count(name: str, value) -> None:
    """Record ``value`` under ``name``; the latest build wins."""
    counters[name] = int(value)
