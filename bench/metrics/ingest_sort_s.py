"""ingest_sort_s: seconds of set-up in the ingest's CSR build after the
deduplication: the program's `repro.ingest.sort` span (symmetrize, casts,
degrees and offsets, the row-major key and its stable argsort and gather,
`core/graph.py` `from_edges`)."""
from bench.harness import spanwin


def read(run):
    return spanwin.setup_s(run, "repro.ingest.sort")
