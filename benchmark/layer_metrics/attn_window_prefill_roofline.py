"""The window band's own work in the traced prefill chunks (a query sees min(t + 1, window) keys; 4 H Dh FLOPs a seen key over 197 TFLOP/s) over the device time under attn_window in the prefill program."""

from harness import readers_window


def read(run):
    return readers_window.attn_window_prefill_roofline(run)
