"""device_ms_per_search.g500: device-busy milliseconds in the traced window
per search completed in it: every device operation, the cohort step's
level programs first (see the breakdown's top operations)."""
from bench.harness import tracewin


def read(run):
    calls = tracewin.traced_calls(run)
    if run.trace is None or not calls:
        return None
    return run.trace["busy_s"] * 1e3 / len(calls)
