"""csr_build_s: seconds in the program's ingest, `core/graph.py`
`from_edges` (deduplication and the CSR build), timed by the harness around
the call."""


def read(run):
    return run.csr_build_s
