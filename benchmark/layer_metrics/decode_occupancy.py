"""Mean decoding slots per decode dispatch, chat cell (paged cache and scheduler)."""

from harness import readers


def read(run):
    return readers.decode_occupancy(run)
