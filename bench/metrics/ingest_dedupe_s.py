"""ingest_dedupe_s: seconds of set-up in the ingest's deduplication: the
program's `repro.ingest.dedupe` span (self loops and duplicate undirected
edges dropped, `core/graph.py` `from_edges`)."""
from bench.harness import spanwin


def read(run):
    return spanwin.setup_s(run, "repro.ingest.dedupe")
