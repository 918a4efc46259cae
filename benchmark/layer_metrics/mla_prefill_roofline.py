"""The expanded prefill attention's share of its roofline: re-expansion and attention FLOPs of the occupied history and the chunk over peak, over device time under attn_mla in the prefill program."""

from harness import readers_mla


def read(run):
    return readers_mla.mla_prefill_roofline(run)
