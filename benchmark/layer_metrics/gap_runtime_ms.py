"""Device-idle time between two programs that lies inside serve.dispatch.wait (launch: the jitted call returned, the program has not started; wake: it ended, the host has not returned), ms a gap, traced tail (harness/gaps.py)."""

from harness import gaps


def read(run):
    return gaps.class_ms(run, gaps.RUNTIME)
