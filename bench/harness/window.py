"""The measured window of a fit cell.

Whole jobs (a fit, or one batch of a stream) run back to back until
``seconds`` have passed since the window opened; the job running then is
finished, never cut. The rate is the rows of every completed job over the
time from the window's start to the end of the last one. Each job returns
only once its result is on the device (``block_until_ready``), so the
clock reads the work done, not its enqueue.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable


@dataclasses.dataclass(frozen=True)
class JobWindow:
    jobs: int
    rows: int
    seconds: float
    job_seconds: tuple[float, ...]

    @property
    def rows_per_s(self) -> float:
        return self.rows / self.seconds


def run_jobs(job: Callable[[int], int], seconds: float, *,
             clock: Callable[[], float] = time.perf_counter) -> JobWindow:
    """Run ``job(i)`` for i = 0, 1, ... (each returns the rows it fitted)
    until ``seconds`` have passed; at least one job runs."""
    t0 = clock()
    rows, times, t_prev = 0, [], t0
    while True:
        rows += int(job(len(times)))
        t = clock()
        times.append(t - t_prev)
        t_prev = t
        if t - t0 >= seconds:
            break
    return JobWindow(jobs=len(times), rows=rows, seconds=t_prev - t0,
                     job_seconds=tuple(times))
