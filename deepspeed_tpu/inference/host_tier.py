"""Host-DRAM second tier for the paged KV-cache — spilled block storage.

HBM pressure used to give the prefix cache exactly one answer: evict
the LRU refcount-zero block and lose its K/V (re-prefill on the next
hit). This module adds the middle rung of the degradation ladder
(docs/KV_TIERING.md): a :class:`HostBlockPool` keeps evict-candidate
blocks in host DRAM — the reproduction of the reference's
ZeRO-Infinity ``swap_tensor`` host-offload capability (PAPER.md layer
5) re-aimed at inference serving — so a later radix hit on a spilled
chain RESTORES the bytes instead of recomputing them.

The pool is deliberately dumb: a dict of contiguous numpy copies under
a byte budget. All tiering POLICY (what spills, when, what a failed
restore degrades to) lives in :mod:`.paged_cache`; all transfer
mechanics (the fixed-width gather/scatter programs, double buffering)
live there too. What this module owns is DURABILITY: every stored
array carries a CRC32 integrity tag computed at put time and verified
at get time, so a corrupted host buffer (bit rot, a stray write, an
injected ``cache.host_corrupt`` fault) surfaces as
:class:`HostCorruption` — the cache discards the poisoned chain and
re-prefills, and NEVER serves wrong K/V as if it were cached truth.

Budget exhaustion is not an error: :meth:`HostBlockPool.put` returns
None and the caller leaves the block device-resident, where plain LRU
eviction — exactly the tier-off behavior — remains the backstop.

The pool has a second consumer beyond spill/restore: the router's
replica-to-replica KV migration (``router._migrate`` +
``paged_cache.migrate_gather``/``land_parked``) stages a finished
prefill's blocks here on the way from a prefill replica's pool to a
decode replica's — the same CRC32-at-put / verify-at-get contract
guarantees a corrupted hand-off degrades to a cold re-prefill instead
of wrong K/V (docs/ROBUSTNESS.md migration ladder).
"""

import zlib
from typing import Dict, Optional, Tuple

import numpy as np

from deepspeed_tpu.utils.env import resolve_flag


class HostCorruption(Exception):
    """A host-tier block failed its CRC32 integrity check at restore
    time — the cache's cue to discard the chain and degrade to a
    cold-miss re-prefill (wrong K/V must never reach attention)."""


def resolve_host_tier(flag: Optional[bool] = None) -> bool:
    """Resolve the host-DRAM KV tier switch.

    Explicit argument wins, else the ``DS_KV_HOST_TIER`` env var
    (``on``/``off``, also ``1``/``0``/``true``/``false``), else OFF —
    the single-tier (device-only) cache is the behavioral
    bit-reference."""
    return resolve_flag("DS_KV_HOST_TIER", flag)


def resolve_host_budget(budget_bytes: Optional[int] = None) -> int:
    """Host-tier byte budget: explicit argument wins, else
    ``DS_KV_HOST_BUDGET_MB`` (default 256 MiB — host DRAM is cheap but
    not free, and an unbounded pool would hide leaks)."""
    if budget_bytes is not None:
        return int(budget_bytes)
    return int(resolve_flag("DS_KV_HOST_BUDGET_MB") * (1 << 20))


class HostBlockPool:
    """CRC-tagged host-DRAM storage for spilled KV blocks.

    One entry holds one pool block's payload as a tuple of contiguous
    numpy arrays — ``(k_blk, v_blk)`` of shape ``[L, bs, Hkv*Dh]``,
    plus the ``(k_scale, v_scale)`` fp32 sidecars ``[L, Hkv]`` when the
    device pool is int8 (the tier composes with ``DS_KV_QUANT=int8`` by
    spilling quantized bytes AND their scales, so a restored block
    dequantizes to exactly what was spilled). Keys are monotonically
    increasing ints minted by :meth:`put`; a key is never reused, so a
    stale reference can only miss, not alias."""

    def __init__(self, budget_bytes: Optional[int] = None):
        self.budget_bytes = resolve_host_budget(budget_bytes)
        # key -> (arrays, crcs, nbytes)
        self._entries: Dict[int, Tuple[tuple, tuple, int]] = {}
        self._next_key = 0
        self.bytes_used = 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: int) -> bool:
        return int(key) in self._entries

    def put(self, arrays: tuple) -> Optional[int]:
        """Store one block's arrays; returns its key, or None when the
        byte budget cannot cover it (the caller's cue to fall back to
        plain device-side eviction — budget exhaustion is a policy
        outcome, not an error)."""
        # ALWAYS copy: ascontiguousarray aliases an already-contiguous
        # input, and a caller-mutated alias would fail its own CRC
        copies = tuple(np.array(a, order="C", copy=True) for a in arrays)
        nbytes = sum(int(c.nbytes) for c in copies)
        if self.bytes_used + nbytes > self.budget_bytes:
            return None
        crcs = tuple(zlib.crc32(c.tobytes()) for c in copies)
        key = self._next_key
        self._next_key += 1
        self._entries[key] = (copies, crcs, nbytes)
        self.bytes_used += nbytes
        return key

    def get(self, key: int) -> tuple:
        """Fetch a block's arrays, verifying every CRC32 tag. Raises
        :class:`HostCorruption` on a mismatch (the entry is NOT
        discarded here — the cache owns the chain-level cleanup) and
        KeyError on a key that was never stored or already discarded."""
        arrays, crcs, _ = self._entries[int(key)]
        for i, (a, crc) in enumerate(zip(arrays, crcs)):
            if zlib.crc32(np.ascontiguousarray(a).tobytes()) != crc:
                raise HostCorruption(
                    f"host block {key} array {i} failed its CRC32 check "
                    f"(stored 0x{crc:08x})")
        return arrays

    def discard(self, key: int) -> None:
        """Drop an entry (idempotent — restore and subtree-removal
        paths may both try to clean the same key)."""
        entry = self._entries.pop(int(key), None)
        if entry is not None:
            self.bytes_used -= entry[2]

    def corrupt(self, key: int) -> None:
        """Flip one byte of a stored block IN PLACE — the chaos/test
        helper behind the real (non-injected) CRC-mismatch path."""
        arrays, _, _ = self._entries[int(key)]
        flat = arrays[0].reshape(-1).view(np.uint8)
        flat[0] ^= 0xFF
