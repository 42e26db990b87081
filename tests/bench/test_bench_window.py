"""The fit rate's window arithmetic, on a fake clock."""
import sys

import pytest

from benchutil import BENCH

sys.path.insert(0, str(BENCH))
from harness.window import run_jobs  # noqa: E402


class Clock:
    def __init__(self, job_seconds):
        self.t, self.steps = 100.0, list(job_seconds)

    def __call__(self):
        return self.t

    def job(self, i):
        self.t += self.steps[i]
        return 1000


def test_finishes_the_job_running_at_the_close():
    c = Clock([4.0, 4.0, 4.0, 4.0])
    w = run_jobs(c.job, 10.0, clock=c)
    assert w.jobs == 3                     # the third ends at 12 s, past 10
    assert w.seconds == pytest.approx(12.0)
    assert w.rows_per_s == pytest.approx(3000 / 12.0)
    assert w.job_seconds == (4.0, 4.0, 4.0)


def test_at_least_one_job():
    c = Clock([30.0])
    w = run_jobs(c.job, 10.0, clock=c)
    assert (w.jobs, w.rows) == (1, 1000)
    assert w.rows_per_s == pytest.approx(1000 / 30.0)


def test_uneven_jobs():
    c = Clock([1.0, 2.5, 0.5, 7.0, 1.0])
    w = run_jobs(c.job, 10.0, clock=c)
    assert w.jobs == 4 and w.seconds == pytest.approx(11.0)
