"""Mean decoding slots per decode dispatch, docs cell (paged cache and scheduler)."""

from harness import readers


def read(run):
    return readers.decode_occupancy(run)
