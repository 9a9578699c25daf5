"""The knee sweep's rule: a rate is sustained when its throughput keeps up
with the offered rate and its queue does not grow; the knee is the highest
rate sustained at that rate and every lower one."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import sweep  # noqa: E402


def rec(rate, thr, first=100.0, last=110.0):
    return {"rate": rate, "throughput_rps": thr, "first_quarter_ms": first,
            "last_quarter_ms": last}


@pytest.mark.parametrize("r,ok", [
    (rec(600, 599.0), True),
    (rec(600, 570.0), False),                     # throughput falls behind
    (rec(600, 600.0, first=100.0, last=140.0), False),   # queue grows
])
def test_sustained(r, ok):
    assert sweep.sustained(r, tolerance=0.02) is ok


def test_knee_is_the_last_rate_of_the_sustained_run():
    recs = [dict(rec(r, r), sustained=s) for r, s in
            [(800, False), (400, True), (600, True), (700, False),
             (900, True)]]
    assert sweep.knee_of(recs) == 600
    assert sweep.knee_of([dict(rec(400, 1), sustained=False)]) is None
