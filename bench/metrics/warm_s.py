"""warm_s: seconds from constructing the entry point (`Engine` or
`BFSServer` with its `GraphSession`) to the end of its warm-up: device
placement of the graph, compilation or compile-cache loads, first runs."""


def read(run):
    return run.warm_s or None
