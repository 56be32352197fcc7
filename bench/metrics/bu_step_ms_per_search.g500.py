"""bu_step_ms_per_search.g500: milliseconds per search in bottom-up level
steps: the program's `repro.level.step` spans with variant `bu` (the B=1
cohort's `cohort_b1_bu` executable and its fence, `engine/level_loop.py`),
summed over each search of the window and averaged."""
from bench.harness import spanwin


def read(run):
    return spanwin.per_search_ms(run, ("repro.level.step",), variant="bu")
