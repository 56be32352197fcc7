"""Whole runs of every cell at a tiny scale on the CPU: a sound run comes
out correct; the control, and the timed path broken underneath, do not.

These skip the harness's look for a chip and drive the rest of a run
(`run_cell`). The faults are planted in the program below its entry
point: a step that returns its state unchanged, and an answer altered
where it is produced. A search has one root, so no batch has a half to
leave out, and one chip has no exchange between chips to leave out.
"""
import time

import numpy as np
import pytest

import tiny

CELLS = ["kron22.g500", "urand22.g500"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    rec, res = tiny.run(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert rec.checked > 0 and rec.compiles_in_window == 0
    assert set(res["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    _, res = tiny.run(cell, use_control=True)
    assert not res["correct"]
    checks = res["checks"]
    assert checks["wrong_levels"]["value"] > 0
    assert checks["bad_parents"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_step_returning_its_state_unchanged_is_caught(cell, monkeypatch):
    """From the window's start every level step hands back the state it
    was given; 8 s later (long after the harness gave up on the answers)
    it raises instead, so the threads stuck in the program can end."""
    from bench.harness import drive
    from repro.engine import level_loop
    real = level_loop.CohortBatchBackend.compute
    window = {}

    def compute(self, state, sync):
        if "t0" not in window:
            return real(self, state, sync)
        if time.perf_counter() > window["t0"] + 8.0:
            raise RuntimeError("fault cleared")
        time.sleep(0.001)
        return state

    monkeypatch.setattr(level_loop.CohortBatchBackend, "compute", compute)
    def opened(*a, _real=drive.closed_loop, **k):
        window["t0"] = time.perf_counter()
        return _real(*a, **k)

    monkeypatch.setattr(drive, "closed_loop", opened)
    _, res = tiny.run(cell, grace=2.0, seconds=0.5)
    assert not res["correct"]
    assert res["checks"]["missing_answers"]["value"] > 0
    while time.perf_counter() < window["t0"] + 9.0:
        time.sleep(0.2)


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered_where_produced_is_caught(cell, monkeypatch):
    from repro.core import bfs as B
    real = B.finalize

    def altered(st):
        parent, level = real(st)
        for b in range(level.shape[0]):
            deepest = int(np.argmax(level[b]))
            if level[b, deepest] > 0:
                level[b, deepest] += 1
        return parent, level

    monkeypatch.setattr(B, "finalize", altered)
    _, res = tiny.run(cell)
    assert not res["correct"]
    assert res["checks"]["wrong_levels"]["value"] > 0



def test_every_seed_searches_the_same_keys_in_the_same_order():
    """The seed draws the checked sample and the warm-up root, never the
    work: two seeds time the same searches."""
    rec_a, _ = tiny.run("kron22.g500", seed=2**31 + 3, seconds=0.5)
    rec_b, _ = tiny.run("kron22.g500", seed=7, seconds=0.5)
    n = min(len(rec_a.calls), len(rec_b.calls))
    assert n > 1
    assert [c["edges"] for c in rec_a.calls[:n]] == \
        [c["edges"] for c in rec_b.calls[:n]]
