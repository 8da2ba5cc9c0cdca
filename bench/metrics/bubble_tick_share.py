"""Executor: the share of the tick loop's (tick, rank) units that the
executor's tick table marks idle, the fill and drain bubble (%), from the
counters ``tp.ticks``, ``tp.ranks`` and ``tp.idle_units`` that the program
records when it builds the loop.  For a given traffic the schedule fixes
it; it moves when the executor runs more or fewer ticks than the schedule
needs.  Under forward-only schedules an idle unit still computes, masked,
so this is compute spent on nothing, not device idle.  None where the
program records no such counters."""


def read(obs):
    try:
        from repro import tracing
    except ImportError:
        return None
    counters = getattr(tracing, "counters", {})
    ticks, ranks = counters.get("tp.ticks"), counters.get("tp.ranks")
    idle = counters.get("tp.idle_units")
    if not ticks or not ranks or idle is None:
        return None
    return 100.0 * idle / (ticks * ranks)
