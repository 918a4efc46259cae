"""The scalar-decay chunk form's share of the RECURRENCE's roofline in prefill: 6 x 128 x 128 FLOPs a value head a valid token over 197 TFLOP/s, or the slot's state read and written once a chunk and the tokens' rows over 819 GB/s, whichever binds, over device time under the scope gdn_chunk."""

from harness import readers_gdn


def read(run):
    return readers_gdn.gdn_chunk_roofline(run)
