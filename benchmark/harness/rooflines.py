"""Operations and bytes the algorithm needs, computed from shapes. The
least time the chip could take for a call is the larger of operations over
peak FLOP/s and bytes over peak bytes/s; a kernel's roofline share is that
time over its measured device time."""


def gpt2_train_flops_per_token(hp: dict, seq: int) -> float:
    """Model FLOPs per trained token, forward and backward, Megatron-LM
    accounting: 6 x every matmul parameter (the tied logit projection
    counts, the embedding lookups do not) + 12 L d S of attention.
    Recomputed operations are not credited."""
    L, d, V = int(hp["n_layer"]), int(hp["n_embd"]), int(hp["vocab_size"])
    per_layer = d * 3 * d + d * d + 2 * d * 4 * d
    return 6.0 * (L * per_layer + d * V) + 12.0 * L * d * seq


def causal_attention_fwd(batch: int, heads: int, seq: int, head_dim: int,
                         itemsize: int = 2):
    """(flops, bytes) of one causal attention forward over [B, H, S, D]:
    two matmuls of 2 S^2 D each per head, half of them under the causal
    mask; q, k, v read once and the output written once."""
    flops = 0.5 * 4.0 * batch * heads * seq * seq * head_dim
    nbytes = 4.0 * batch * heads * seq * head_dim * itemsize
    return flops, nbytes


def paged_decode_bytes(occupied_blocks: int, block_size: int, kv_heads: int,
                       head_dim: int, layers: int, itemsize: int = 2):
    """Bytes one decode step has to read from the paged KV pool: K and V of
    every occupied block, in every layer. (The q and output rows are
    thousands of times smaller and are left out.)"""
    return 2.0 * occupied_blocks * block_size * kv_heads * head_dim \
        * itemsize * layers


def min_seconds(flops: float, nbytes: float, peaks: dict):
    """(least seconds, "compute" | "memory")."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
