"""Device idle inside the program's serve.dispatch spans per dispatch, from the traced tail (spans and device operations on one clock)."""

from harness import provenance


def read(run):
    return provenance.dispatch_idle_ms(run)
