"""The check must fail what it exists to catch.

Each test drives a whole tiny run (no look for a chip) with the timed
path broken underneath, or with the reference's lower-precision plans in
the program's place (the control), and requires ``correct`` to come out
false: once for each fault the cell can have.
"""
import dataclasses

import numpy as np
import pytest

import tiny


def _reindex(monkeypatch, share):
    """Of the ``n`` requests a chunk solves, only the first
    ``share(n)`` get their own plans; the others are answered from them."""
    from repro.fleet import planner
    plan_many = planner.FleetPlanner.plan_many

    def broken(self, scenarios, *args, **kwargs):
        recs = plan_many(self, scenarios, *args, **kwargs)
        k = max(1, share(len(recs)))
        return [recs[i % k] for i in range(len(recs))]

    monkeypatch.setattr(planner.FleetPlanner, "plan_many", broken)


def half_batch_left_out(monkeypatch):
    """Only the first half of each chunk is solved; the rest is answered
    from it."""
    _reindex(monkeypatch, lambda n: (n + 1) // 2)


def answer_altered(monkeypatch):
    """Each plan's block size moved to its nearest other grid point where
    the record is made."""
    from repro.fleet import planner
    record = planner.FleetPlan.record

    def altered(self, i):
        rec = record(self, i)
        row = np.asarray(self.grid[i]).ravel()
        other = row[row != rec.n_c]
        if other.size == 0:
            return rec
        n_c = int(other[np.argmin(np.abs(other - rec.n_c))])
        return dataclasses.replace(rec, n_c=n_c)

    monkeypatch.setattr(planner.FleetPlan, "record", altered)


FAULTS = [
    ("bound-solve-open", half_batch_left_out),
    ("bound-solve-open", answer_altered),
]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    r = tiny.run(cell)
    assert r["correct"] is False, r["checked"]


@pytest.mark.parametrize("cell", sorted(tiny.OVERRIDES))
def test_control_is_not_correct(cell):
    r = tiny.run(cell, substitute="control")
    assert r["correct"] is False, r["checked"]


@pytest.mark.parametrize("cell", sorted(tiny.OVERRIDES))
def test_sound_run_is_correct(cell):
    r = tiny.run(cell)
    assert r["correct"] is True, r["checked"]
