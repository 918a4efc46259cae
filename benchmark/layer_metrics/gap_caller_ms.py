"""Device-idle time between two programs that no program span covers: the loop around step(), here the benchmark's load generator, ms a gap, traced tail (harness/gaps.py)."""

from harness import gaps


def read(run):
    return gaps.class_ms(run, gaps.CALLER)
