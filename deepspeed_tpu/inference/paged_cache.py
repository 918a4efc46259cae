"""Block-paged KV-cache: fixed-size blocks + per-request block tables.

The static engine preallocates a ``[L, B, S_max, Hkv, Dh]`` cache, so one
long request holds ``S_max`` slots for every row and the whole batch's
memory is ``B * S_max`` tokens regardless of what is actually in flight
(the reproduction of the reference's global Context workspace, ref:
ops/transformer/inference/transformer_inference.py:113 softmax_context).
This module is the PagedAttention answer (Kwon et al., SOSP '23): K/V
live in a pool of fixed-size blocks ``[L, N_blocks, block, Hkv*Dh]``,
each serving slot owns an ordered list of block ids (its block table),
and a free-list allocator hands blocks out on demand — cache memory
scales with tokens in flight, fragmentation is bounded by one partial
block per request, and a finished request's blocks return to the pool
immediately.

**One layout in HBM.** A cached token is ONE row of ``Hkv*Dh`` lanes,
its kv heads folded side by side. With the heads a dimension of their
own the device pads 25 heads to 32 sublanes and 64 lanes to 128, the
compiler stores the pool compactly with the block index minor, and
every serving dispatch re-laid every layer's pool out for the scatter
and the kernel and back (68-74% of device time, PERF.md PR 25). The
folded row is row-major on the device as it is here (1,600 lanes pad to
1,664: 4%), so the entry parameter, the layer loop's carried state
(``[L*N_blocks, block, Hkv*Dh]``, a bitcast; layer ``l`` addresses
block ``b`` at ``b + l*N_blocks``: inference/engine.py ``_scan_layers``)
and the Mosaic kernel's operand (ops/attention/paged.py) are the same
bytes and nothing copies the pool. Everything here indexes dimension 1
by block id and never looks inside a block.

**Shared-prefix caching** (``prefix_cache=True`` / ``DS_PREFIX_CACHE=on``,
vLLM automatic prefix caching + SGLang RadixAttention): blocks carry
REFCOUNTS, and a host-side radix index (:mod:`.prefix_index`) maps full
block-sized token chunks to the pool blocks already holding their K/V.
Admission matches a new prompt's longest cached prefix and maps those
blocks into the slot's table read-only (refcount++), charging the free
list only for the uncached suffix; a divergence *inside* a block is
handled by copy-on-write (device-copy the partially-matching block into
a fresh one, overwrite from the divergence point). A finished request's
indexed blocks stay resident at refcount 0 — evictable — and block
reclaim becomes LRU over those instead of whole-request preemption.
``prefix_cache=False`` (the default) is bit-identical to the pre-prefix
allocator and stays the behavioral reference.

Host-side bookkeeping (tables, lengths, refcounts, the free list, the
radix index) is plain numpy — it changes every scheduler iteration and
must never trigger a recompile; the device arrays (``k``/``v`` pools)
thread functionally through the engine's donated ``prefill_into_slot``
/ ``decode_slots`` programs, and the only device work this module ever
issues is the one COW block copy (a single compiled program, warmed at
serving startup).

**Five kinds of cache state** share this allocator, the block tables and
the two serving programs' calls (inference/dialect.py holds one record
each; ``dialect.refuse`` raises by name for what cannot yet live with
one): K and V pools (the GPT blocks); a full layers' pool beside a window
RING per slot (hybrid.py); ONE pool of latent rows (latent.py); K and V
pools beside a per-slot TAIL (cca.py); and a paged pool for some layers
beside a per-slot RECURRENT STATE for the others (linear.py). The last is
the first whose slot costs memory before it holds a token (41.9 MB a slot
for Kimi-Linear against 8,960 bytes a token; 8.5 MB against 1,024 for
Jamba2-3B): a byte budget buys the slots first and blocks with what is
left (``slot_state_bytes``).

Block id 0 is RESERVED as the trash block: the slot programs route
writes for masked-out lanes (chunk padding, inactive slots) there, so
the compiled scatter needs no branch.

**Host-DRAM tier** (``host_tier=True`` / ``DS_KV_HOST_TIER=on``,
docs/KV_TIERING.md): refcount-zero INDEXED blocks can spill to a
:class:`~deepspeed_tpu.inference.host_tier.HostBlockPool` instead of
dying at eviction — the reproduction of the reference's ZeRO-Infinity
``swap_tensor`` offload re-aimed at inference. A low-watermark spill
daemon (:meth:`PagedKVCache.spill_tick`, driven once per serving step,
never on the admission critical path) gathers up to ``transfer_blocks``
LRU spill candidates with ONE fixed-width compiled gather and harvests
the bytes to host on the NEXT tick (double-buffered: the device→host
copy overlaps a full decode step). A prefix match that lands on
host-tier links restores them block-by-block through a fixed-width
compiled scatter, drawing restore targets from the FREE LIST only.
Both programs are warmed at :meth:`PagedKVCache.warm_host_tier`, so the
steady state compiles ZERO new programs. Every failure rung degrades,
never corrupts: a CRC-bad host block discards its whole chain
(cold-miss re-prefill), a failed spill leaves the block device-resident
behind exponential backoff, and an exhausted host budget falls back to
plain eviction — exactly the tier-off behavior.
"""

import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.utils.env import resolve_flag
from deepspeed_tpu.inference import dialect
from deepspeed_tpu.inference.host_tier import (
    HostBlockPool, HostCorruption, resolve_host_tier)
from deepspeed_tpu.inference.prefix_index import PrefixIndex, PrefixMatch
from deepspeed_tpu.models import gpt as gpt_lib
from deepspeed_tpu.models.gpt import GPTConfig
from deepspeed_tpu.ops.quantizer import resolve_kv_quant


class CacheExhausted(Exception):
    """The free list cannot cover an allocation — the scheduler's cue to
    evict-and-requeue instead of OOMing the device."""


def resolve_prefix_cache(flag: Optional[bool] = None) -> bool:
    """Resolve the shared-prefix cache switch.

    Explicit argument wins, else the ``DS_PREFIX_CACHE`` env var
    (``on``/``off``, also ``1``/``0``/``true``/``false``), else OFF —
    the refcount-free allocator is the behavioral bit-reference."""
    return resolve_flag("DS_PREFIX_CACHE", flag)


# The three block-copy programs, each over the TUPLE of pools — (k, v),
# or with int8 pools (k, v, k_scale, v_scale) — so a block's per-(block,
# kv_head) scales travel with its payload and a shared, spilled or
# restored block dequantizes to the values it was written with. Block ids
# are traced, so each program is one compiled entry per cache shape. The
# serving engine wires its model engine's own jits of these functions in
# (``copy_fn`` / ``gather_fn`` / ``scatter_fn``); a standalone cache runs
# the module-level ones.
def copy_block(pools, src, dst):
    """Copy ONE pool block (every layer) ``src`` -> ``dst``: the device
    half of copy-on-write. Pools are donated, so the copy is in place in
    HBM."""
    return tuple(p.at[:, dst].set(p[:, src]) for p in pools)


def gather_blocks(pools, ids):
    """Pull ``len(ids)`` blocks out of the pools: the device half of a
    host-tier spill (spill_tick) and of a replica-to-replica migration
    (migrate_gather). ``ids`` is a FIXED-width vector — short batches pad
    with the trash block (its lanes are gathered and then simply not
    stored). Pools are NOT donated: the gathered copy rides out
    asynchronously while the pools keep serving decode."""
    return tuple(p[:, ids] for p in pools)


def scatter_block(pools, blocks, dst):
    """Write ONE restored block, ``blocks`` = its slice of every pool,
    back at ``dst``: the device half of a host→device restore
    (_dispatch_restore) and of a migration landing (land_parked). Pools
    are donated: the write is in place in HBM, mirroring the COW copy."""
    return tuple(p.at[:, dst].set(b) for p, b in zip(pools, blocks))


def chunk_blocks(start, n, bs: int, nb: int):
    """The entries of its slot's table (``nb`` blocks of ``bs``) in which a
    prefill chunk of ``n`` real tokens at position ``start`` has a row:
    (first entry, how many). None for ``n`` 0, and none past the table's
    end. Integer arithmetic that holds for traced ``start`` and ``n``
    (:func:`write_chunk`'s choice of the windows it writes) and for Python
    ones (the scheduler's ``blocks`` count)."""
    first = start // bs
    end = (start + n + bs - 1) // bs
    end = end - (end - nb) * (end > nb)     # min(end, nb), for a tracer too
    return first, (end - first) * (end > first) * (n > 0)


# the most bytes one window of a chunk's write may hold. The compiler (a
# v5e's, read ahead of time) splits a gather whose slice passes 512 KiB by
# lanes, and each part first slices the POOL out: 1.7 GiB of temporaries in
# the dots.vlm1 cell's prefill program, whose block of 512 rows is 640 KiB.
# Half of that
WRITE_WINDOW_BYTES = 256 << 10


def write_window(bs: int, row_bytes: int) -> int:
    """Rows in one window of :func:`write_chunk`: the whole block where it
    fits ``WRITE_WINDOW_BYTES`` (GPT-2 XL's 16 rows are 50 KiB), else the
    block halved until it does (128 of a latent block's 512 rows), and
    never a part of the device's tile of 16 rows, so that the pool seen in
    windows is the pool's own bytes."""
    g = bs
    while g % 32 == 0 and g * row_bytes > WRITE_WINDOW_BYTES:
        g //= 2
    return g


def write_chunk(pool, table_row, start, n_valid, rows, base=0):
    """Write one slot's prompt chunk into ``pool`` ``[N, bs, lanes]`` as the
    few WHOLE windows it touches (a window: a block, or an aligned part of a
    large one, :func:`write_window`). ``rows`` ``[C, lanes]`` sit at
    positions ``start + [0, C)`` of the slot's row, the first ``n_valid`` of
    them real; ``table_row`` ``[NB]`` is the slot's block table, ``base`` the
    layer's offset into the pool. The run lies in at most ``(C + g - 2) // g
    + 1`` windows of ``g`` rows (static: 5 for 64 rows in blocks of 16, 5
    for 512 in windows of 128, 2 for 512 in 512): those are read, the real
    rows laid over them at ``start % g``, everything else of them kept, and
    the windows written back. A window with no real row
    (:func:`chunk_blocks`) is read from and written to the trash block
    instead, so no live row is written twice. Outside the trash block the
    pool is bit for bit what ``pool.at[blk, pos % bs].set(rows)`` over the
    real rows leaves; that scatter moves a row an index, one after the
    other, and this one a window (PERF.md, PR 48). No branch on alignment: a
    branch that returns a pool copies it."""
    C, (N, bs, lanes), NB = rows.shape[0], pool.shape, table_row.shape[0]
    g = write_window(bs, lanes * pool.dtype.itemsize)
    per = bs // g                           # windows a block
    n = (C + g - 2) // g + 1
    first, count = chunk_blocks(start, n_valid, g, NB * per)
    i = jnp.arange(n, dtype=jnp.int32)
    w = first + i                           # the windows of the slot's row
    blk = table_row[jnp.clip(w // per, 0, NB - 1)] + base
    ids = jnp.where(i < count, blk * per + w % per, base * per)
    windows = pool.reshape(N * per, g, lanes)
    old = windows[ids].reshape(n * g, lanes)
    at = start % g
    laid = jax.lax.dynamic_update_slice_in_dim(
        old, rows.astype(pool.dtype), at, axis=0)
    r = jnp.arange(n * g, dtype=jnp.int32)[:, None]
    real = jnp.logical_and(r >= at, r < at + n_valid)
    return windows.at[ids].set(
        jnp.where(real, laid, old).reshape(n, g, lanes)).reshape(pool.shape)


_default_cow = jax.jit(copy_block, donate_argnums=(0,))
_default_gather = jax.jit(gather_blocks)
_default_scatter = jax.jit(scatter_block, donate_argnums=(0,))


class PagedKVCache:
    """Pool + allocator + per-slot block tables (+ optional prefix index).

    num_blocks is the HBM-budget watermark made concrete: either passed
    directly or derived from ``hbm_budget_bytes`` via the per-token cache
    cost (models.gpt.kv_bytes_per_token). ``watermark`` free blocks are
    held back at admission time so every active slot can always grow into
    its next decode block without immediate eviction.

    With ``prefix_cache=True`` every mapped block carries a refcount
    (shared prefix blocks count once per slot mapping them); a block is
    in exactly ONE of three states: on the free list, held (refcount >
    0), or cached (indexed, refcount 0, reclaimable in LRU order).
    ``copy_fn(pools, src, dst) -> pools`` performs the COW block copy —
    the serving engine wires the engine's donated program in; standalone
    caches fall back to a module-level jitted copy.

    With ``kv_quant="int8"`` (or ``DS_KV_QUANT=int8``) the pools store
    int8 with fp32 per-(block, kv_head) scales in parallel ``k_scale`` /
    ``v_scale`` pools ``[L, N_blocks, Hkv]``, which ride in ``pools``
    behind k and v, so scales travel with blocks on every copy. ``"off"``
    (default) keeps the fp pools byte-identical to the unquantized cache
    — the bit-reference.

    With ``host_tier=True`` (or ``DS_KV_HOST_TIER=on``) refcount-zero
    indexed blocks spill to host DRAM under HBM pressure instead of
    being evicted outright, and a prefix match on a spilled chain
    restores the bytes instead of re-prefilling (module docstring;
    docs/KV_TIERING.md). The tier requires the prefix cache — only
    indexed blocks are worth keeping on ANY tier — so with
    ``prefix_cache=False`` the flag is inert and the device-only
    allocator stays the bit-reference. ``gather_fn(pools, ids) ->
    blocks`` / ``scatter_fn(pools, blocks, dst) -> pools`` override the
    transfer programs (the serving engine wires the engine's jitted
    ones in); standalone caches fall back to module-level jitted
    defaults.
    """

    def __init__(self, cfg: GPTConfig, *, num_slots: int,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 hbm_budget_bytes: Optional[int] = None,
                 dtype=jnp.bfloat16, max_seq_len: Optional[int] = None,
                 watermark: Optional[int] = None, faults=None,
                 prefix_cache: bool = False,
                 copy_fn: Optional[Callable] = None,
                 tracer=None,
                 kv_quant: Optional[str] = None,
                 host_tier: Optional[bool] = None,
                 host_budget_bytes: Optional[int] = None,
                 transfer_blocks: int = 4,
                 spill_watermark: Optional[int] = None,
                 gather_fn: Optional[Callable] = None,
                 scatter_fn: Optional[Callable] = None):
        self.cfg = cfg
        # telemetry hook (telemetry/tracer.RequestTracer): COW copies
        # and index-block reclaims land in the serving timeline; None
        # (standalone caches, telemetry off) records nothing
        self.tracer = tracer
        # fault-injection hook (utils/faults.FaultInjector): the
        # ``cache.allocate`` / ``cache.ensure`` sites can fire a
        # synthetic CacheExhausted so the scheduler's eviction path runs
        # under test without actually shrinking the pool;
        # ``cache.match`` degrades a prefix lookup to a miss and
        # ``cache.cow`` fails the copy-on-write before any bookkeeping
        self.faults = faults
        self.block_size = int(block_size)
        self.num_slots = int(num_slots)
        self.blocks_per_slot, self.tokens_per_slot = gpt_lib.decode_geometry(
            cfg, self.block_size, max_seq_len)
        self.dtype = jnp.dtype(dtype)
        # KV quantization: int8 pools + fp32 per-(block, kv_head) scale
        # pools ("off" keeps the fp pools bit-identical to before)
        self.kv_quant = resolve_kv_quant(kv_quant)
        self.quantized = self.kv_quant == "int8"
        d = self.dialect = dialect.of(cfg)
        Hkv = cfg.kv_heads
        # what cannot yet live with bounded window state, with a latent
        # pool, with per-slot tails or a recurrent state raises here, by
        # name
        for on, what in ((prefix_cache, "prefix sharing (prefix_cache)"),
                         (self.quantized, "int8 KV pools (kv_quant)"),
                         (resolve_host_tier(host_tier) and prefix_cache,
                          "the host tier (host_tier)")):
            if on:
                dialect.refuse(cfg, what)
        self.ring_blocks = d.ring_blocks(cfg, self.block_size)
        self.pool_dtype = jnp.dtype(jnp.int8) if self.quantized \
            else self.dtype
        self.bytes_per_token = d.bytes_per_token(cfg, self.pool_dtype)
        # what the slots hold whatever their length, whole from
        # construction on: a slot costs these before it holds a token, so
        # slots, not blocks, are what this memory buys
        (self.window_bytes, self.cca_tail_bytes, self.recurrent_state_bytes,
         self.conv_tail_bytes) = (
            self.num_slots * b
            for b in d.slot_bytes(cfg, self.block_size, self.pool_dtype))
        # what the slots cost out of the same budget as the blocks
        self.slot_state_bytes = self.window_bytes \
            + self.recurrent_state_bytes + self.conv_tail_bytes
        # scale overhead: 2 pools (K and V) × L layers × Hkv heads × fp32
        # per block — amortized it is 2*L*Hkv*4/block_size bytes/token
        self.scale_bytes_per_block = (2 * cfg.n_layers * Hkv * 4) \
            if self.quantized else 0
        if num_blocks is None:
            if hbm_budget_bytes:
                per_block = (self.bytes_per_token * self.block_size
                             + self.scale_bytes_per_block)
                # the rings, the tails and the recurrent state come out
                # of the same budget: what the slots cost is spent first
                num_blocks = int((hbm_budget_bytes - self.slot_state_bytes)
                                 // per_block)
            else:
                # default pool: the static reservation's worth of blocks
                # (num_slots full sequences) — usage accounting then shows
                # how far actual tokens-in-flight undercut it. Counted in
                # blocks, not bytes: under kv_quant the scale sidecar must
                # not shave the pool below its own slots' capacity
                num_blocks = self.num_slots * self.blocks_per_slot
        # +1: block 0 is the reserved trash block, never allocated
        self.num_blocks = int(num_blocks) + 1
        if self.num_blocks < 2:
            raise ValueError(
                f"HBM budget covers {self.num_blocks - 1} blocks; the "
                f"pool needs at least 1 allocatable block")
        self.k, self.v = d.new_state(cfg, self.num_blocks, self.block_size,
                                     self.num_slots, self.pool_dtype)
        # what the decode kernel cuts its tile by (ServingEngine's kv_steps)
        self.tile_row_bytes = d.tile_row_bytes(cfg, d.pool(self.k))
        if self.quantized:
            self.k_scale = jnp.zeros((cfg.n_layers, self.num_blocks, Hkv),
                                     jnp.float32)
            self.v_scale = jnp.zeros_like(self.k_scale)
        else:
            self.k_scale = None
            self.v_scale = None
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self._owned: List[List[int]] = [[] for _ in range(num_slots)]
        # len(_owned[slot]) as an array, stored wherever a slot's list
        # changes (never rebuilt): the scheduler's one comparison a step
        # for the slots that need a block (ServingEngine._grow_decoding)
        self.owned_count = np.zeros((num_slots,), np.int32)
        self._refcount = np.zeros((self.num_blocks,), np.int32)
        # a slot's row: its blocks in the (full layers') pool and, behind
        # them, the ids of its ring blocks in a window layer, which never
        # change
        self.tables = np.zeros(
            (num_slots, self.blocks_per_slot + self.ring_blocks), np.int32)
        if self.ring_blocks:
            self.tables[:, self.blocks_per_slot:] = 1 + np.arange(
                num_slots * self.ring_blocks).reshape(num_slots, -1)
        self.lengths = np.zeros((num_slots,), np.int32)
        self.active = np.zeros((num_slots,), bool)
        self.watermark = num_slots if watermark is None else int(watermark)
        self.prefix_cache = bool(prefix_cache)
        self.index: Optional[PrefixIndex] = \
            PrefixIndex(self.block_size) if self.prefix_cache else None
        self.copy_fn = copy_fn
        # host-DRAM second tier (docs/KV_TIERING.md): gated on the
        # prefix index because only INDEXED blocks spill — a block no
        # future request can match is dead weight on any tier. With the
        # index absent the knob is inert (bit-reference either way).
        self.host_tier = resolve_host_tier(host_tier) and \
            self.index is not None
        self.host_pool: Optional[HostBlockPool] = \
            HostBlockPool(host_budget_bytes) if self.host_tier else None
        self.gather_fn = gather_fn
        self.scatter_fn = scatter_fn
        self.transfer_blocks = max(1, int(transfer_blocks))
        # spill trigger: one transfer batch ABOVE the admission
        # watermark by default, so spilling starts before admission
        # control begins holding requests back
        self.spill_watermark = (self.watermark + self.transfer_blocks) \
            if spill_watermark is None else int(spill_watermark)
        # blocks whose bytes are mid-flight (queued gather not yet
        # harvested): excluded from EVERY reclaim/eviction predicate and
        # from free-list returns until the harvest settles them
        self._in_transfer: set = set()
        # replica-to-replica migration landings (docs/ROBUSTNESS.md):
        # rid -> (block ids, prefix length). Parked blocks are neither
        # free nor owned nor indexed — invisible to every reclaim path —
        # until the request's admission adopts them or a drain/fallback
        # drops them back onto the free list
        self._parked: Dict = {}
        self._pending_spill = None   # (ids, gathered device arrays)
        self._spill_cooldown = 0     # ticks until the next spill attempt
        self._spill_backoff = 1      # cooldown applied on the next failure
        self._restore_ms: List[float] = []
        self.peak_used_blocks = 0
        self.peak_tokens_in_flight = 0
        # prefix-cache counters (mirrored into serving stats / bench rows)
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_tokens_saved = 0
        self.cow_copies = 0
        self.cache_block_evictions = 0
        # host-tier counters
        self.host_spills = 0
        self.host_restores = 0
        self.host_restore_failures = 0
        self.host_spill_aborts = 0
        self.host_budget_refusals = 0
        # migration counters (landings adopted / chains dropped)
        self.parked_adopted = 0
        self.parked_aborts = 0

    # -- accounting ----------------------------------------------------
    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        """Blocks not on the free list — held by slots OR resident in
        the prefix cache (both occupy HBM)."""
        return (self.num_blocks - 1) - len(self._free)

    @property
    def ring_rows_allocated(self) -> int:
        """Token places of the window layers' rings (inference/hybrid.py),
        all slots and window layers; 0 for a model without such layers."""
        if not self.ring_blocks:
            return 0
        return self.num_slots * self.ring_blocks * self.block_size \
            * self.cfg.n_window_layers

    @property
    def ring_rows_used(self) -> int:
        """Of those, the places that hold a token a query can still see:
        ``min(length, attn_window)`` a slot (a free slot's length is 0)."""
        if not self.ring_blocks:
            return 0
        return int(np.minimum(self.lengths, self.cfg.attn_window).sum()) \
            * self.cfg.n_window_layers

    def ring_wrapped(self, length: int) -> bool:
        """Whether a slot of ``length`` tokens is past its window: its
        window layers then read a full ring and its writes reuse places."""
        return bool(self.ring_blocks) and length > self.cfg.attn_window

    @property
    def held_blocks(self) -> int:
        """Blocks mapped into at least one slot table (refcount > 0)."""
        return int((self._refcount > 0).sum())

    @property
    def shared_blocks(self) -> int:
        """Blocks mapped by MORE than one slot — the sharing win."""
        return int((self._refcount > 1).sum())

    def _reclaimable(self, bid: int) -> bool:
        """The ONE reclaim-eligibility predicate: refcount zero AND not
        mid-transfer. Every eviction/availability path must use it — a
        block whose bytes are in flight to host must not be handed out
        (the harvest would scatter stale truth over a live block)."""
        return self._refcount[bid] == 0 and bid not in self._in_transfer

    @property
    def cached_blocks(self) -> int:
        """Indexed blocks no slot holds: resident, reclaimable (LRU)."""
        if self.index is None:
            return 0
        return self.index.evictable_count(self._reclaimable)

    @property
    def host_blocks(self) -> int:
        """Blocks resident on the host tier (spilled, restorable)."""
        return len(self.host_pool) if self.host_pool is not None else 0

    @property
    def host_bytes(self) -> int:
        """Host-DRAM bytes the spilled blocks occupy."""
        return self.host_pool.bytes_used if self.host_pool is not None \
            else 0

    @property
    def tokens_in_flight(self) -> int:
        return int(self.lengths.sum())

    def stats(self) -> Dict[str, float]:
        """Allocator state for bench rows and operators: block counts by
        state, internal fragmentation of slot tables (tail-block waste:
        allocated-but-unwritten token positions over allocated capacity),
        and the prefix-cache counters."""
        cap_tokens = int(self.owned_count.sum()) * self.block_size
        frag = (1.0 - self.tokens_in_flight / cap_tokens) if cap_tokens \
            else 0.0
        return {
            "num_blocks": self.num_blocks - 1,
            "free_blocks": self.free_blocks,
            "used_blocks": self.used_blocks,
            "held_blocks": self.held_blocks,
            "shared_blocks": self.shared_blocks,
            "cached_blocks": self.cached_blocks,
            "fragmentation": round(float(frag), 4),
            "tokens_in_flight": self.tokens_in_flight,
            "peak_used_blocks": self.peak_used_blocks,
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            "prefix_tokens_saved": self.prefix_tokens_saved,
            "cow_copies": self.cow_copies,
            "cache_block_evictions": self.cache_block_evictions,
            "host_blocks": self.host_blocks,
            "host_bytes": self.host_bytes,
            "window_bytes": self.window_bytes,
            "recurrent_state_bytes": self.recurrent_state_bytes,
            "host_spills": self.host_spills,
            "host_restores": self.host_restores,
            "host_restore_failures": self.host_restore_failures,
            "host_spill_aborts": self.host_spill_aborts,
            "host_budget_refusals": self.host_budget_refusals,
            "parked_blocks": sum(len(b) for b, _ in self._parked.values()),
            "parked_adopted": self.parked_adopted,
            "parked_aborts": self.parked_aborts,
        }

    def used_block_bytes(self) -> int:
        """Bytes actually held by allocated blocks — what the bench's
        'paged peak HBM' row reports (scales with tokens in flight,
        block-quantized). Includes the per-block scale overhead when the
        pool is int8."""
        return self.used_blocks * (self.block_size * self.bytes_per_token
                                   + self.scale_bytes_per_block)

    def static_equivalent_bytes(self, batch: int,
                                max_seq_len: Optional[int] = None) -> int:
        """What the static [B, S_max] cache would reserve for the same
        traffic — the comparison row."""
        s = max_seq_len or self.cfg.max_seq_len
        return batch * s * self.bytes_per_token

    def blocks_for(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.block_size)

    def at_capacity(self, slot: int) -> bool:
        """True when the slot's cache has consumed its whole block
        budget: the next decode write would CLAMP into the last live
        block (inference/engine.py masks it to the trash block), so the
        scheduler must finish the request before the kernel runs."""
        return int(self.lengths[slot]) >= self.tokens_per_slot

    # -- admission control ---------------------------------------------
    def _peek_match(self, tokens) -> PrefixMatch:
        """LRU-neutral prefix lookup (admission precheck)."""
        if self.index is None or tokens is None or len(tokens) < 2:
            return PrefixMatch()
        return self.index.match(tokens, max_tokens=len(tokens) - 1,
                                touch=False)

    def blocks_needed(self, n_tokens: int, tokens=None) -> int:
        """Fresh blocks an allocation would draw from the pool after
        prefix sharing (a COW divergence still needs its fresh copy).
        Only DEVICE-tier matched links are free; a host-tier hit costs
        one fresh block too — its restore target."""
        m = self._peek_match(tokens)
        dev = m.tiers.count("device") if m.tiers else len(m.block_ids)
        return self.blocks_for(n_tokens) - dev

    def available_blocks(self, tokens=None) -> int:
        """Free blocks plus LRU-reclaimable cached blocks, EXCLUDING any
        block a match on ``tokens`` would map (a chain block at refcount
        0 cannot both be shared into the slot and reclaimed for it).
        Host-tier links never pin: their keys live in a separate
        namespace and their restore targets are charged by
        :meth:`blocks_needed`."""
        n = len(self._free)
        if self.index is not None:
            m = self._peek_match(tokens)
            if m.tiers:
                pinned = {b for b, t in zip(m.block_ids, m.tiers)
                          if t == "device"}
            else:
                pinned = set(m.block_ids)
            if m.cow_src is not None:
                pinned.add(m.cow_src)
            n += self.index.evictable_count(
                lambda b: self._reclaimable(b) and b not in pinned)
        return n

    def can_admit(self, n_tokens: int, tokens=None,
                  watermark: Optional[int] = None) -> bool:
        """Admission-control check: fresh blocks for the (uncached part
        of the) prompt available AND the watermark reserve stays intact
        so live slots can keep growing. Shared prefix blocks are free —
        admission charges only the uncached suffix."""
        wm = self.watermark if watermark is None else int(watermark)
        return self.available_blocks(tokens) >= \
            self.blocks_needed(n_tokens, tokens) + wm

    # -- allocator -----------------------------------------------------
    def allocate(self, slot: int, n_tokens: int, tokens=None) -> int:
        """Reserve blocks covering ``n_tokens`` for a fresh slot.

        With the prefix cache on and the prompt's ``tokens`` given, the
        longest cached prefix is mapped in read-only (shared blocks,
        refcount++) and only the uncached suffix draws fresh blocks; a
        mid-block divergence copy-on-writes the partially-matching block.
        Returns the number of prefix tokens already resident — the
        slot's ``lengths`` starts there and prefill begins at that
        offset (0 on a miss / with the cache off)."""
        if not 0 <= slot < self.num_slots:
            raise ValueError(f"slot {slot} out of range "
                             f"[0, {self.num_slots})")
        if self.active[slot] or self._owned[slot]:
            raise ValueError(f"slot {slot} is already allocated; free() "
                             f"it before re-allocating")
        self._maybe_inject("cache.allocate", slot)
        need_total = self.blocks_for(n_tokens)
        if need_total > self.blocks_per_slot:
            raise ValueError(
                f"{n_tokens} tokens need {need_total} blocks > per-slot "
                f"table width {self.blocks_per_slot}")
        m = self._match_for_allocate(tokens)
        # every fault site above fired and every validation ran; from
        # here the bookkeeping must be atomic (claim -> check -> commit,
        # with rollback on the one remaining failure: pool shortage)
        pinned = list(m.block_ids)
        if m.cow_src is not None:
            pinned.append(m.cow_src)
        for bid in pinned:
            self._refcount[bid] += 1      # claim: un-reclaimable below
        fresh_need = need_total - len(m.block_ids)
        avail = len(self._free)
        if self.index is not None:
            avail += self.index.evictable_count(self._reclaimable)
        if fresh_need > avail:
            for bid in pinned:
                self._refcount[bid] -= 1  # rollback the claim
            raise CacheExhausted(
                f"need {fresh_need} fresh blocks "
                f"({need_total} total, {len(m.block_ids)} shared), "
                f"{avail} available")
        ids = [self._pop_free() for _ in range(fresh_need)]
        if m.cow_src is not None:
            # the divergent/partial block: device-copy into the first
            # fresh block (table position len(chain)); the suffix
            # prefill overwrites it from the divergence point on
            self._cow(m.cow_src, ids[0])
            self._refcount[m.cow_src] -= 1   # pin released post-copy
        for bid in ids:
            self._refcount[bid] = 1
        all_ids = m.block_ids + ids
        self._owned[slot] = list(all_ids)
        self.owned_count[slot] = len(all_ids)
        self.tables[slot, :self.blocks_per_slot] = 0
        self.tables[slot, :len(all_ids)] = all_ids
        self.lengths[slot] = m.matched
        self.active[slot] = True
        if self.index is not None and tokens is not None:
            if m.matched > 0:
                self.prefix_hits += 1
                self.prefix_tokens_saved += m.matched
            else:
                self.prefix_misses += 1
        self._mark()
        return m.matched

    def _match_for_allocate(self, tokens) -> PrefixMatch:
        """The real (LRU-touching) prefix match, with its fault sites:
        ``cache.match`` degrades the lookup to a miss, ``cache.cow``
        fails the copy-on-write — both BEFORE any bookkeeping mutates,
        so an injected failure leaves the allocator untouched."""
        if self.index is None or tokens is None or len(tokens) < 2:
            return PrefixMatch()
        f = self._fire("cache.match")
        if f is not None and f.kind == "cache_exhausted":
            return PrefixMatch()          # degraded: serve as a cold miss
        m = self.index.match(tokens, max_tokens=len(tokens) - 1)
        if "host" in m.tiers:
            m = self._restore_match(m)
        if m.cow_src is not None:
            f = self._fire("cache.cow")
            if f is not None and f.kind == "cache_exhausted":
                raise CacheExhausted(
                    "injected copy-on-write failure at cache.cow "
                    f"({self.free_blocks} blocks actually free)")
        return m

    def _restore_match(self, m: PrefixMatch) -> PrefixMatch:
        """Bring a matched chain's host-tier links back on device, in
        prefix order. Each restore costs one FREE-LIST block (restores
        never reclaim — the admission path must stay cheap and must not
        cannibalize the very cache it is hitting). The first link that
        cannot restore — free list dry, injected ``cache.restore``
        fault, CRC corruption — TRUNCATES the match there: the already-
        restored prefix is kept, the tail degrades to a cold-miss
        re-prefill. Always correct tokens, merely slower."""
        for i, tier in enumerate(m.tiers):
            if tier == "device":
                continue
            ok = False
            if self._free:
                f = self._fire("cache.restore")
                if f is not None and f.kind == "cache_exhausted":
                    # injected transfer failure: the host entry SURVIVES
                    # (a later match retries it); this match degrades
                    self.host_restore_failures += 1
                else:
                    f = self._fire("cache.host_corrupt")
                    if f is not None and f.kind == "cache_exhausted":
                        # flip a real byte so the REAL CRC machinery,
                        # not a shortcut, drives the degrade path
                        self.host_pool.corrupt(m.block_ids[i])
                    ok = self._dispatch_restore(m.block_ids[i], i, m)
            if not ok:
                return self._truncate_match(m, i)
        return m

    def _dispatch_restore(self, key: int, i: int, m: PrefixMatch) -> bool:
        """One host→device block restore: CRC-verified fetch, H2D copy,
        fixed-shape scatter into a free block, index flip to device.
        Returns False on corruption (after discarding the poisoned
        subtree — every descendant's prefix runs through the bad
        chunk). Mutates ``m`` in place on success."""
        t0 = time.perf_counter()
        try:
            payload = self.host_pool.get(key)
        except HostCorruption:
            dev, hosts = self.index.remove_subtree(key)
            for hk in hosts:
                self.host_pool.discard(hk)
            for bid in dev:
                # device descendants at refcount 0 go straight back to
                # the free list (they were index-resident, so they are
                # not on it); held or mid-transfer blocks are settled by
                # their release / harvest instead
                if self._refcount[bid] == 0 and \
                        bid not in self._in_transfer:
                    self._free.append(bid)
            self.host_restore_failures += 1
            if self.tracer is not None:
                self.tracer.event("cache_restore_corrupt", key=int(key),
                                  dropped_host=len(hosts),
                                  dropped_device=len(dev))
            return False
        bid = self._free.pop()
        self._run_scatter(payload, bid)
        self.index.to_device(key, bid)
        self.host_pool.discard(key)
        m.block_ids[i] = bid
        m.tiers[i] = "device"
        self.host_restores += 1
        ms = (time.perf_counter() - t0) * 1000.0
        self._restore_ms.append(ms)
        if self.tracer is not None:
            self.tracer.event("cache_restore", block=bid, key=int(key),
                              ms=round(ms, 3))
        return True

    def _truncate_match(self, m: PrefixMatch, i: int) -> PrefixMatch:
        """Degrade: keep the usable device prefix ``[0, i)``, drop the
        rest. The COW candidate hangs off the FULL chain's tail, so a
        truncated match cannot carry it."""
        return PrefixMatch(block_ids=m.block_ids[:i], tiers=m.tiers[:i],
                           matched=i * self.block_size)

    def ensure_capacity(self, slot: int, n_tokens: int) -> None:
        """Grow the slot's table until it covers ``n_tokens`` (append).
        When the free list is dry, reclaim least-recently-used cached
        blocks (refcount 0) from the prefix index first — request
        preemption is the scheduler's LAST resort, not the first."""
        if not self.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        self._maybe_inject("cache.ensure", slot)
        need = self.blocks_for(n_tokens)
        if need > self.blocks_per_slot:
            raise ValueError(
                f"{n_tokens} tokens exceed the per-slot capacity "
                f"{self.tokens_per_slot}")
        while len(self._owned[slot]) < need:
            bid = self._pop_free()
            self._refcount[bid] = 1
            self.tables[slot, len(self._owned[slot])] = bid
            self._owned[slot].append(bid)
            self.owned_count[slot] += 1
        self._mark()

    def advance(self, slot: int, n_tokens: int) -> None:
        """Record ``n_tokens`` newly written to the slot's cache."""
        new_len = int(self.lengths[slot]) + int(n_tokens)
        assert new_len <= len(self._owned[slot]) * self.block_size, \
            (slot, new_len, len(self._owned[slot]))
        self.lengths[slot] = new_len
        self.peak_tokens_in_flight = max(self.peak_tokens_in_flight,
                                         self.tokens_in_flight)

    def advance_each(self, slots: np.ndarray) -> None:
        """:meth:`advance` by ONE token for every slot of ``slots`` (an
        index array without repeats: the slots a batched decode step
        wrote), with one test of the tables and one reading of the peak
        for all of them."""
        self.lengths[slots] += 1
        # every slot's, the untouched ones' too: no slot ever holds more
        # tokens than its blocks cover
        assert (self.lengths <= self.owned_count * self.block_size).all(), \
            (slots, self.lengths, self.owned_count)
        self.peak_tokens_in_flight = max(self.peak_tokens_in_flight,
                                         self.tokens_in_flight)

    def needs_block(self, slots: np.ndarray) -> np.ndarray:
        """Which of ``slots`` (a mask over all slots) cannot take one more
        token as their tables stand: the next token lies past the blocks
        they own, or they are :meth:`at_capacity`."""
        return slots & ((self.lengths >= self.owned_count * self.block_size)
                        | (self.lengths >= self.tokens_per_slot))

    def capacity_tokens(self, slot: int) -> int:
        """Token positions the slot's allocated blocks cover — the cap
        on how far a speculative chunk may advance before rollback."""
        return len(self._owned[slot]) * self.block_size

    def horizon_budget(self, slot: int, n_tokens: int) -> int:
        """Opportunistic capacity grant for a fused multi-step decode
        (docs/MULTISTEP.md): try to grow the slot's table to cover
        ``n_tokens`` total positions, but — unlike :meth:`ensure_capacity`
        — treat a dry pool as a smaller horizon, not a failure. Returns
        the TOTAL token positions actually granted; the scheduler caps
        the slot's in-program emission budget there, so horizon tokens
        beyond the guaranteed first never trigger eviction (the plain
        one-token preamble already secured that one). The in-scan write
        path needs no rollback: a lane frozen at its budget stops
        advancing its length, so no write ever lands past the grant."""
        want = min(int(n_tokens), self.tokens_per_slot)
        if want > self.capacity_tokens(slot):
            try:
                self.ensure_capacity(slot, want)
            except CacheExhausted:
                pass
        return min(self.capacity_tokens(slot), self.tokens_per_slot)

    def rollback(self, slot: int, n_tokens: int) -> None:
        """Shrink the slot's logical length to ``n_tokens`` and RELEASE
        any owned tail block the shorter length no longer covers — the
        speculative-decode rollback contract: a rejected draft chunk
        that straddled a block edge must not leave the now-unused tail
        block referenced in the block table (it would silently pin a
        pool block per reject until the request finished). Stale K/V
        inside the kept partial block is safe: the next chunk rewrites
        those positions before any query attends them.

        Hardening: only a non-negative length within the currently
        allocated capacity is a legal rollback target (growing is
        ``advance``'s job), and only blocks this slot OWNS are released
        — shared prefix blocks sit below the prompt boundary, which a
        rollback can never cross (``n_tokens`` >= the pre-chunk length
        >= the prompt length)."""
        if not self.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        n_tokens = int(n_tokens)
        if not 0 <= n_tokens <= self.capacity_tokens(slot):
            raise ValueError(
                f"rollback target {n_tokens} outside the allocated "
                f"capacity [0, {self.capacity_tokens(slot)}] of slot "
                f"{slot}")
        keep = self.blocks_for(n_tokens)
        while len(self._owned[slot]) > keep:
            bid = self._owned[slot].pop()
            self.owned_count[slot] -= 1
            self.tables[slot, len(self._owned[slot])] = 0
            self._release(bid)
        self.lengths[slot] = n_tokens

    def free(self, slot: int) -> None:
        """Release the slot's references. Idempotent: freeing an already-
        free slot is a no-op (retry/requeue paths may race a finish).
        A block whose refcount drops to 0 returns to the free list —
        unless the prefix index holds it, in which case it stays
        resident as reclaimable cache."""
        if not self.active[slot] and not self._owned[slot]:
            self.tables[slot, :self.blocks_per_slot] = 0
            self.lengths[slot] = 0
            return
        for bid in reversed(self._owned[slot]):
            self._release(bid)
        self._owned[slot] = []
        self.owned_count[slot] = 0
        self.tables[slot, :self.blocks_per_slot] = 0
        self.lengths[slot] = 0
        self.active[slot] = False

    def register_prefix(self, slot: int, tokens) -> int:
        """Publish the slot's FULL prompt blocks into the prefix index
        (called once the prompt is completely prefilled, so every full
        block's K/V is final — full blocks are never written again).
        Chunks already cached keep their existing block; the slot's
        duplicate stays private. Returns newly registered blocks."""
        if self.index is None or tokens is None:
            return 0
        if not self.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        n_full = min(len(tokens) // self.block_size,
                     len(self._owned[slot]))
        if int(self.lengths[slot]) < n_full * self.block_size:
            raise ValueError(
                f"slot {slot} holds {int(self.lengths[slot])} tokens; "
                f"cannot register {n_full} full blocks before they are "
                f"written")
        return self.index.insert(
            np.asarray(tokens, np.int32), self._owned[slot][:n_full],
            # a re-registered chunk that had spilled flips back to
            # device on the slot's fresh copy; its host bytes are
            # redundant the moment the flip lands
            on_host_displaced=(self.host_pool.discard
                               if self.host_pool is not None else None))

    def warm_cow(self) -> None:
        """Compile the COW copy program up front (trash-block self-copy)
        so the first real divergence — possibly inside a CompileWatch-
        guarded steady state — hits a warm cache."""
        if self.prefix_cache:
            self._run_cow(np.int32(0), np.int32(0))

    def warm_host_tier(self) -> None:
        """Compile the spill gather and restore scatter up front on
        trash-block lanes, so every steady-state transfer hits a warm
        cache — the CompileWatch(0) contract (docs/KV_TIERING.md)."""
        if not self.host_tier:
            return
        ids = np.zeros((self.transfer_blocks,), np.int32)
        arrs = self._run_gather(ids)
        payload = tuple(np.asarray(a[:, 0])
                        for a in jax.device_get(arrs))
        self._run_scatter(payload, 0)

    # -- host-tier spill daemon ----------------------------------------
    def spill_tick(self) -> int:
        """One spill-daemon tick — the serving loop drives this once per
        step, OFF the admission critical path. Harvests the previous
        tick's in-flight gather (one batched D2H pull, overlapped with
        the decode step that ran in between — the double buffer), then,
        under free-list pressure, dispatches the next fixed-width gather
        over the LRU spill candidates. Returns blocks landed on host
        this tick."""
        if not self.host_tier:
            return 0
        landed = self._harvest_spill()
        if self._pending_spill is not None:
            return landed
        if self._spill_cooldown > 0:
            self._spill_cooldown -= 1
            return landed
        if len(self._free) >= self.spill_watermark:
            return landed
        f = self._fire("cache.spill")
        if f is not None and f.kind == "cache_exhausted":
            # injected transfer failure: the candidates stay device-
            # resident; exponential backoff before the retry
            self._note_spill_failure()
            return landed
        ids = self.index.spill_candidates(self._reclaimable,
                                          self.transfer_blocks)
        if not ids:
            return landed
        padded = np.zeros((self.transfer_blocks,), np.int32)
        padded[:len(ids)] = ids       # short batches pad with trash lanes
        arrs = self._run_gather(padded)
        self._in_transfer.update(ids)
        self._pending_spill = (list(ids), arrs)
        if self.tracer is not None:
            self.tracer.event("cache_spill", blocks=[int(b) for b in ids])
        return landed

    def _harvest_spill(self) -> int:
        """Settle the in-flight gather: ONE batched device→host pull for
        the whole buffer, then per block either commit (store on host,
        flip the index tag, free the device block) or abort (the block
        was re-claimed or unindexed while its bytes flew — the device
        copy stays authoritative)."""
        if self._pending_spill is None:
            return 0
        ids, arrs = self._pending_spill
        self._pending_spill = None
        host = jax.device_get(arrs)
        landed = 0
        for i, bid in enumerate(ids):
            self._in_transfer.discard(bid)
            if self._refcount[bid] != 0 or bid not in self.index:
                self.host_spill_aborts += 1
                if self._refcount[bid] == 0 and bid not in self.index:
                    # unindexed mid-flight (corruption cleanup / release
                    # of a displaced chain): _release deferred to us, so
                    # this is the block's single return to the free list
                    self._free.append(bid)
                continue
            payload = tuple(np.asarray(a[:, i]) for a in host)
            key = self.host_pool.put(payload)
            if key is None:
                # budget refusal is policy, not failure: the block stays
                # device-resident and plain eviction remains its fate
                self.host_budget_refusals += 1
                self._note_spill_failure()
                continue
            self.index.to_host(bid, key)
            self._free.append(bid)
            self.host_spills += 1
            landed += 1
        if landed:
            self._spill_backoff = 1
        return landed

    def _note_spill_failure(self) -> None:
        """Exponential-backoff cooldown (in daemon ticks, capped): a
        failing transfer path must not be hammered every step."""
        self._spill_cooldown = self._spill_backoff
        self._spill_backoff = min(self._spill_backoff * 2, 64)

    def abort_transfers(self) -> int:
        """Abort every in-flight spill synchronously — the drain/retire
        contract: a replica must settle its transfer state BEFORE
        ``pending_snapshot(release=True)`` hands its requests away. The
        un-harvested gather is dropped (the candidates simply stay
        device-resident; JAX discards the orphaned computation) and the
        in-transfer set is settled so every block is releasable. Returns
        how many spills were aborted."""
        aborted = 0
        if self._pending_spill is not None:
            ids, _ = self._pending_spill
            self._pending_spill = None
            aborted = len(ids)
            self.host_spill_aborts += aborted
        for bid in sorted(self._in_transfer):
            self._in_transfer.discard(bid)
            if self._refcount[bid] == 0 and not (
                    self.index is not None and bid in self.index):
                self._free.append(bid)
        return aborted

    # -- replica-to-replica KV migration (docs/ROBUSTNESS.md) ----------
    # The disaggregated prefill/decode fleet generalizes the host tier's
    # CRC-verified transfer path into a replica→replica channel: the
    # SOURCE cache gathers a finished prefill's whole chain through host
    # DRAM (per-array CRC32 at put time), the DESTINATION lands the
    # blocks free-list-only as a PARKED chain its admission later
    # adopts. Every failure rung — budget refusal, CRC mismatch, dry
    # free list, a replica dying mid-flight — degrades to a cold
    # re-prefill on the decode side, never a wrong token.

    def warm_migration(self) -> None:
        """Compile the transfer gather/scatter up front on trash-block
        lanes (same programs :meth:`warm_host_tier` warms, but the
        migration channel needs them with the host tier OFF too), so a
        role'd fleet's steady state compiles nothing — CompileWatch(0)."""
        ids = np.zeros((self.transfer_blocks,), np.int32)
        arrs = self._run_gather(ids)
        payload = tuple(np.asarray(a[:, 0]) for a in jax.device_get(arrs))
        self._run_scatter(payload, 0)

    def migrate_gather(self, slot: int, pool: HostBlockPool) -> Dict:
        """Source half of a migration: pull the slot's owned chain
        through host DRAM in ``transfer_blocks``-wide batches (the same
        fixed-width gather the spill daemon uses — short batches pad
        with trash lanes) and :meth:`HostBlockPool.put` each block, so
        every array carries a CRC32 tag the landing verifies. Returns
        ``{"keys", "length", "n_blocks"}`` — the migration's
        ``kv_handle``. On ANY failure (budget refusal raises
        :class:`CacheExhausted`) the already-stored keys are discarded
        and the slot is left untouched: the source still owns its
        blocks, so the caller can fall back to a cold re-prefill."""
        if not self.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        chain = list(self._owned[slot])
        keys: List[int] = []
        try:
            for start in range(0, len(chain), self.transfer_blocks):
                ids = chain[start:start + self.transfer_blocks]
                padded = np.zeros((self.transfer_blocks,), np.int32)
                padded[:len(ids)] = ids
                host = jax.device_get(self._run_gather(padded))
                for i in range(len(ids)):
                    payload = tuple(np.asarray(a[:, i]) for a in host)
                    key = pool.put(payload)
                    if key is None:
                        raise CacheExhausted(
                            f"migration host budget refused block "
                            f"{len(keys) + 1}/{len(chain)}")
                    keys.append(key)
        except Exception:
            for k in keys:
                pool.discard(k)
            raise
        return {"keys": keys, "length": int(self.lengths[slot]),
                "n_blocks": len(chain)}

    def land_parked(self, rid, keys: List[int], pool: HostBlockPool,
                    length: int) -> int:
        """Destination half: CRC-verified fetch of each migrated block
        and a free-list-ONLY scatter into this pool (landings never
        evict — the decode side's cache must not be cannibalized by an
        incoming migration; a dry free list raises
        :class:`CacheExhausted` and the request re-prefills cold). The
        landed chain parks under ``rid`` until :meth:`adopt_parked`. A
        mid-landing failure (corruption, dry list) returns every landed
        block to the free list and re-raises — the host entries stay
        the caller's to discard."""
        if rid in self._parked:
            raise ValueError(f"request {rid!r} already has a parked chain")
        landed: List[int] = []
        try:
            for key in keys:
                payload = pool.get(key)      # CRC32 -> HostCorruption
                if not self._free:
                    raise CacheExhausted(
                        f"migration landing needs a free block "
                        f"({len(landed)}/{len(keys)} landed)")
                bid = self._free.pop()
                self._run_scatter(payload, bid)
                landed.append(bid)
        except Exception:
            self._free.extend(reversed(landed))
            raise
        self._parked[rid] = (landed, int(length))
        self._mark()
        return len(landed)

    def has_parked(self, rid) -> bool:
        """True when a migrated chain is parked for ``rid``."""
        return rid in self._parked

    def adopt_parked(self, slot: int, rid) -> int:
        """Install the parked chain as ``slot``'s owned blocks — the
        migration analog of :meth:`allocate`'s prefix hit: the slot
        starts with ``length`` tokens already resident (refcount 1,
        private — migrated blocks are never shared) and prefill resumes
        at that offset, covering only the already-emitted tail tokens.
        Returns the resident prefix length."""
        if not 0 <= slot < self.num_slots:
            raise ValueError(f"slot {slot} out of range "
                             f"[0, {self.num_slots})")
        if self.active[slot] or self._owned[slot]:
            raise ValueError(f"slot {slot} is already allocated; free() "
                             f"it before adopting a parked chain")
        bids, length = self._parked.pop(rid)
        for bid in bids:
            self._refcount[bid] = 1
        self._owned[slot] = list(bids)
        self.owned_count[slot] = len(bids)
        self.tables[slot, :self.blocks_per_slot] = 0
        self.tables[slot, :len(bids)] = bids
        self.lengths[slot] = length
        self.active[slot] = True
        self.parked_adopted += 1
        self._mark()
        return length

    def drop_parked(self, rid) -> int:
        """Return a parked chain's blocks to the free list (idempotent
        — fallback and drain paths may both try). Returns blocks freed."""
        entry = self._parked.pop(rid, None)
        if entry is None:
            return 0
        bids, _ = entry
        self._free.extend(reversed(bids))
        self.parked_aborts += 1
        return len(bids)

    def abort_parked(self) -> int:
        """Drop every parked chain — the drain/retire contract, same
        discipline as :meth:`abort_transfers`: a replica settles its
        migration landings BEFORE ``pending_snapshot(release=True)``
        hands its requests away (each dropped chain's request re-
        prefills cold on a survivor). Returns chains dropped."""
        rids = list(self._parked)
        for rid in rids:
            self.drop_parked(rid)
        return len(rids)

    def drain_restore_ms(self) -> List[float]:
        """Hand the per-restore wall-clock samples (ms) to the caller
        (the serving engine feeds its ``kv_host_restore_ms`` histogram
        on the sampled cadence) and reset the buffer."""
        out = self._restore_ms
        self._restore_ms = []
        return out

    @property
    def scales(self) -> Optional[tuple]:
        """``(k_scale, v_scale)`` of int8 pools, else None: the serving
        programs' ``scales`` operand."""
        return None if self.k_scale is None else (self.k_scale, self.v_scale)

    @property
    def pools(self) -> tuple:
        """The cache's device state as ONE value, what every block copy
        takes and every serving program hands back: ``(k, v)``, with
        int8 pools ``(k, v, k_scale, v_scale)``; k and v as the model's
        dialect made them (inference/dialect.py ``new_state``)."""
        return (self.k, self.v) + (self.scales or ())

    @pools.setter
    def pools(self, pools) -> None:
        self.k, self.v, *scales = pools
        if scales:
            self.k_scale, self.v_scale = scales

    def _run_gather(self, ids: np.ndarray):
        """Dispatch the fixed-width spill gather."""
        return (self.gather_fn or _default_gather)(self.pools, ids)

    def _run_scatter(self, payload: tuple, bid: int) -> None:
        """Dispatch the restore scatter, rebinding the pools from its
        donated outputs."""
        blocks = tuple(jax.device_put(a) for a in payload)
        self.pools = (self.scatter_fn or _default_scatter)(
            self.pools, blocks, np.int32(bid))

    # -- internals -----------------------------------------------------
    def _run_cow(self, src, dst) -> None:
        """Dispatch the COW copy program, rebinding the pools from its
        donated outputs."""
        self.pools = (self.copy_fn or _default_cow)(self.pools, src, dst)

    def _cow(self, src: int, dst: int) -> None:
        self._run_cow(np.int32(src), np.int32(dst))
        self.cow_copies += 1
        if self.tracer is not None:
            self.tracer.event("cow", src=src, dst=dst)

    def _pop_free(self) -> int:
        """Next usable block: the free list, else the LRU refcount-zero
        cached block (unregistered from the index). Raises
        :class:`CacheExhausted` when neither can supply one."""
        if self._free:
            return self._free.pop()
        if self.index is not None:
            bid = self.index.pop_evictable(self._reclaimable)
            if bid is not None:
                self.cache_block_evictions += 1
                if self.tracer is not None:
                    self.tracer.event("cache_evict_block", block=bid)
                return bid
        raise CacheExhausted("free list empty and no reclaimable "
                             "cached blocks")

    def _release(self, bid: int) -> None:
        """Drop one reference with hardening: a foreign or already-free
        block id is a bookkeeping bug and raises instead of silently
        corrupting the pool (load-bearing once blocks are shared)."""
        if not 0 < bid < self.num_blocks:
            raise ValueError(f"foreign block id {bid} (pool has blocks "
                             f"1..{self.num_blocks - 1}; 0 is the trash "
                             f"block)")
        if self._refcount[bid] <= 0:
            raise ValueError(f"double free of block {bid}")
        self._refcount[bid] -= 1
        # a mid-transfer block is never returned here even when it drops
        # unindexed — the harvest's abort path is its single freer (two
        # freers would race into a double free-list entry)
        if self._refcount[bid] == 0 and bid not in self._in_transfer \
                and not (self.index is not None and bid in self.index):
            self._free.append(bid)

    def _mark(self):
        self.peak_used_blocks = max(self.peak_used_blocks, self.used_blocks)

    def _fire(self, site: str):
        if self.faults is None:
            return None
        return self.faults.fire(site)

    def _maybe_inject(self, site: str, slot: int) -> None:
        f = self._fire(site)
        if f is not None and f.kind == "cache_exhausted":
            raise CacheExhausted(
                f"injected cache exhaustion at {site} (slot {slot}, "
                f"{self.free_blocks} blocks actually free)")
