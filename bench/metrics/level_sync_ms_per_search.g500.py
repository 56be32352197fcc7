"""level_sync_ms_per_search.g500: milliseconds per search in the level
driver's host syncs: the program's `repro.level.sync` spans (one
`device_get` of the step's scalars and its unpacking per level, plus one
after init, `engine/level_loop.py`), summed over each search of the window
and averaged."""
from bench.harness import spanwin


def read(run):
    return spanwin.per_search_ms(run, ("repro.level.sync",))
