"""host_gap_ms_per_search.g500: device-idle milliseconds in the traced
window per search completed in it. Nearly all of it is one gap at the end
of each search: the result path (`LevelDriver.finalize`'s copy of `parent`
and `level` to the host, then `Engine`'s tree depth and edge accounting
over them), with the level driver's per-level syncs a small rest."""
from bench.harness import tracewin


def read(run):
    calls = tracewin.traced_calls(run)
    if run.trace is None or not calls:
        return None
    return run.trace["idle_s"] * 1e3 / len(calls)
