"""plan_resolve_s: seconds of set-up resolving the program's executables:
the `repro.plan.resolve` spans before the window (each plan's compile,
compile-cache or disk load, and its first dispatch;
`engine/session.py` `_PlanExecutable`)."""
from bench.harness import spanwin


def read(run):
    return spanwin.setup_s(run, "repro.plan.resolve")
