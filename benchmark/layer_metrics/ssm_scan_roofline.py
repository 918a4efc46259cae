"""The ssm_scan kernel's share of its roofline by the bytes: the slot's state read and written once a chunk and every token's x, delta, y, B, C rows over 819 GB/s, over kernel time. Reads low by construction: the scan is bound by the vector unit, which has no published peak (the reader's line says element operations a second)."""

from harness import readers_ssm


def read(run):
    return readers_ssm.ssm_scan_roofline(run)
