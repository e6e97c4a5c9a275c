"""The job-boundary window arithmetic, with a fake clock (CPU, no program)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run as harness  # noqa: E402


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def drive(job_walls, seconds, stall_before=None, stall_s=0.0):
    clock = FakeClock()
    started = []

    def run_job(k):
        if k == stall_before:
            clock.now += stall_s  # something other than the program runs
        started.append(clock.now)
        clock.now += job_walls[k]

    marks, rss = harness.run_window(run_job, seconds, clock=clock,
                                    maxrss=lambda: 0)
    return marks, started, rss


def test_window_ends_on_a_job_boundary_and_counts_whole_jobs():
    walls = [3.0, 4.5, 2.5, 3.5, 3.0, 9.0]
    marks, started, rss = drive(walls, seconds=10.0)
    # jobs start while less than 10 s have passed: at 0, 3, 7.5; not at 10
    assert [s - 100.0 for s in started] == [0.0, 3.0, 7.5]
    assert marks[-1] - marks[0] == 10.0 and len(rss) == len(marks)
    rate, window_s = harness.window_rate(marks, reads_per_job=1000)
    assert window_s == 10.0 and rate == 3 * 1000 / 10.0


def test_a_job_that_starts_just_inside_is_never_abandoned():
    marks, started, _ = drive([4.0, 5.9, 7.0, 1.0], seconds=9.95)
    assert len(started) == 3  # the third starts at 9.9 and runs to 16.9
    rate, window_s = harness.window_rate(marks, 10)
    assert abs(window_s - 16.9) < 1e-9 and abs(rate - 30 / 16.9) < 1e-9


def test_a_stall_between_two_jobs_lowers_reads_per_s():
    walls = [2.0] * 10
    calm, _, _ = drive(walls, seconds=9.0)
    stalled, _, _ = drive(walls, seconds=9.0, stall_before=2, stall_s=1.5)
    calm_rate, _ = harness.window_rate(calm, 1000)
    stalled_rate, _ = harness.window_rate(stalled, 1000)
    assert calm_rate == 1000 / 2.0
    assert stalled_rate < calm_rate * 0.9
    # the median job would not have moved: every job still took 2 s
    assert sorted(b - a for a, b in zip(stalled, stalled[1:]))[2] == 2.0
