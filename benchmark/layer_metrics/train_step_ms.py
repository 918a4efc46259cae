"""Median fully synced step of the window, host clock (training engine, runtime/engine.py)."""

from harness import readers


def read(run):
    return readers.train_step_ms(run)
