"""result_host_ms_per_search.g500: milliseconds per search of host work on
the answer once it is on the host: the program's `repro.result.convert`
(sentinels to -1, casts; `core/bfs.py` `finalize`) and
`repro.result.assemble` spans (stacking, tree depth and edge accounting;
`engine/engine.py`), summed over each search of the window and averaged."""
from bench.harness import spanwin


def read(run):
    return spanwin.per_search_ms(
        run, ("repro.result.convert", "repro.result.assemble"))
