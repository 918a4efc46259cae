"""The chunkwise rule's share of the RECURRENCE's roofline in prefill: the same FLOPs a token and the slot's state read and written once a chunk, over device time under the scope kda_chunk."""

from harness import readers_kda


def read(run):
    return readers_kda.kda_chunk_roofline(run)
