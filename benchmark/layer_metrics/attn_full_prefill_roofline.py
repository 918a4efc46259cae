"""The causal triangle's work in the traced prefill chunks (a query sees t + 1 keys) over the chip's peaks, over the device time under attn_full in the prefill program: low where the whole row is attended."""

from harness import readers_window


def read(run):
    return readers_window.attn_full_prefill_roofline(run)
