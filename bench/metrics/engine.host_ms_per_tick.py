"""Host time per engine tick: each ``engine.tick`` span in the traced
window minus its ``engine.host_sync`` children (the blocking reads of the
tick's results), averaged over the ticks.  Moves ``itl_p95_ms``."""
from bench import trace as tr


def read(run):
    ticks = tr.spans(run.trace.host, "engine.tick", run.t0, run.t1)
    syncs = tr.spans(run.trace.host, "engine.host_sync", run.t0, run.t1)
    if not ticks:
        return None
    return sum(tr.self_ns(t, syncs) for t in ticks) / len(ticks) / 1e6
