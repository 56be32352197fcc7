"""device_idle_share.g500: share of the traced window in which no operation
ran on the device, in %, in the Graph500 cells."""
from bench.harness import tracewin


def read(run):
    return tracewin.idle_share(run)
