"""result_transfer_ms_per_search.g500: milliseconds per search copying the
answer to the host: the program's `repro.result.transfer` spans (the
device-to-host copies of `parent` and `level` in `core/bfs.py`
`finalize`), summed over each search of the window and averaged."""
from bench.harness import spanwin


def read(run):
    return spanwin.per_search_ms(run, ("repro.result.transfer",))
