"""flash_fwd's share of its roofline from shapes (ops/attention/flash.py); an earlier line says which bound."""

from harness import readers


def read(run):
    return readers.flash_fwd_roofline(run)
