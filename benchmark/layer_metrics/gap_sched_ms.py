"""Device-idle time between two programs that lies inside the scheduler's own serve.* spans (pull, emit, bookkeeping, admission, the decode step's preparation), ms a gap, traced tail (harness/gaps.py)."""

from harness import gaps


def read(run):
    return gaps.class_ms(run, gaps.SCHED)
