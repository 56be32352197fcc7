"""Run a cell with the control in the program's place; `correct` must come
out false.

    python bench/control.py --workload kron22.g500 --seed 7 --seconds 10

Same arguments and result line as `run.py`. The control is the plain
reference with every vertex's adjacency scan capped at its first 32
entries (`harness/systems.py`, `CONTROL_CAP`): an inexact search, the
shortcut an early-exit pull would take, so it breaks the exact-levels
guarantee the configurations state. Its readings are the upper ends the
limits in `harness/cell.py` (`LIMITS`) were set below. The benchmark's own
runs never run it.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402

if __name__ == "__main__":
    code = run.main(use_control=True)
    sys.stdout.flush()
    os._exit(code)
