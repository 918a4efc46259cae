"""Rows of the window layers' rings that hold a token a query can still see (min(length, window) a slot) over the rows allocated, at the window's peak of seated slots, from the program's count."""

from harness import readers_window


def read(run):
    return readers_window.window_ring_fill_share(run)
