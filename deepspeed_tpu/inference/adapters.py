"""Multi-tenant LoRA adapter serving: registry + paged adapter pool.

One base model, many tenants: ``runtime/lora.py`` trains and exports
rank-r adapters, but merging them into the base (``merge_lora``) means
one fleet per tenant. S-LoRA (Sheng et al., 2023) and Punica (Chen et
al., 2023) showed that thousands of UNMERGED adapters can share one
base if (a) adapter weights live in a paged device pool, and (b) the
decode program applies them with gathered low-rank matmuls indexed by a
per-slot adapter table — traced data, never a jit static, so one
compiled program serves any mix of adapters and base-only slots.

This module is the host-side half of that design (the gathered matmul
lives in ``models/gpt._dense`` + the ``lora=`` operand of the engine's
serving programs):

- **Registry**: ``register(adapter_id, source)`` parses the
  ``runtime/lora.py`` adapter-only export (an ``.npz`` path or the
  ``adapter_state_dict`` mapping), validates every leaf against the
  base kernels, folds ``lora_scale`` into B once in fp32, and stages
  the result host-side in rank-block chunks. Registration touches no
  device memory — thousands of tenants can register against a pool
  that holds only the hot few.
- **Paged pool**: per-target device pools ``a[t] [L, NB, in_t, rb]`` /
  ``b[t] [L, NB, rb, out_t]`` paged over the RANK axis: an adapter of
  rank r occupies ``ceil(r / rank_block)`` blocks recorded in its block
  row. The allocator reuses ``paged_cache.py`` idioms verbatim: block 0
  is a permanent all-zeros trash block (a base-only slot's table row is
  all zeros, so its gathered contribution is exactly ``+0.0`` — bit
  parity with the pre-subsystem stream), a LIFO free list, per-adapter
  refcounts, and LRU eviction of refcount-zero residents when the pool
  fills. Loads go through ONE jitted scatter program (traced dst, all
  targets as a pytree) warmed at construction so a mid-run adapter
  load never compiles.
- **Degradation**: the ``cache.adapter_load`` fault site fires before
  any pool state moves. ``cache_exhausted`` (and a genuinely full
  pool, and an unregistered id) raise :class:`AdapterLoadError`;
  ``device_error`` raises the usual retryable error. The serving
  engine maps both onto a structured per-request ``error`` terminal
  state — the batch keeps serving, never wrong tokens.

docs/ADAPTERS.md has the full contract, including the interplay
matrix with spec-decode / int8 KV / the prefix cache.
"""

import math
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.runtime.lora import DEFAULT_TARGETS
from deepspeed_tpu.utils import faults as faults_lib
from deepspeed_tpu.utils.env import resolve_flag

__all__ = ["AdapterLoadError", "AdapterPool", "resolve_lora_serve"]


class AdapterLoadError(RuntimeError):
    """An adapter could not be made pool-resident (unregistered id,
    pool exhausted with every resident adapter pinned, or an injected
    ``cache.adapter_load`` exhaustion). The serving engine degrades the
    owning request to the structured ``error`` terminal state."""


def resolve_lora_serve(override=None) -> bool:
    """``DS_LORA_SERVE``: explicit argument wins, then env, then the
    declared off-default (base-only serving is the bit-reference)."""
    return resolve_flag("DS_LORA_SERVE", override)


def _load_blocks_fn(a_pool, b_pool, a_chunk, b_chunk, dst):
    """Write one rank-block of every target into pool slot ``dst``.
    ``dst`` is traced data, so one compiled program serves every load."""
    a_pool = {t: a_pool[t].at[:, dst].set(
        a_chunk[t].astype(a_pool[t].dtype)) for t in a_pool}
    b_pool = {t: b_pool[t].at[:, dst].set(
        b_chunk[t].astype(b_pool[t].dtype)) for t in b_pool}
    return a_pool, b_pool


_load_blocks = jax.jit(_load_blocks_fn, donate_argnums=(0, 1))


class AdapterPool:
    """Adapter registry + fixed-size paged device pool (module
    docstring has the design; docs/ADAPTERS.md the contract).

    - ``engine``: the :class:`InferenceEngine` whose base kernels size
      the per-target pools (and whose mesh places them).
    - ``pool_mb`` / ``pool_blocks``: pool capacity as a MiB budget
      (``DS_LORA_POOL_MB`` default) or an explicit block count
      (override wins; tests use it to force eviction).
    - ``max_rank`` / ``rank_block``: largest accepted adapter rank and
      the rank granularity of one block (``DS_LORA_MAX_RANK`` /
      ``DS_LORA_RANK_BLOCK``). Together they fix the STATIC width of
      every per-slot adapter-table row: ``ceil(max_rank / rank_block)``.
    - ``faults`` / ``tracer`` / ``hooks``: the chaos injector for the
      ``cache.adapter_load`` site, an optional trace-event sink, and
      optional ``{"on_hit","on_load","on_evict"}`` counter callbacks
      (the serving engine wires its ``serving_adapter_*`` counters in).
    """

    def __init__(self, engine, *, pool_mb: Optional[float] = None,
                 pool_blocks: Optional[int] = None,
                 max_rank: Optional[int] = None,
                 rank_block: Optional[int] = None,
                 faults: Optional[faults_lib.FaultInjector] = None,
                 tracer=None,
                 hooks: Optional[Mapping[str, Callable]] = None):
        self.engine = engine
        self.faults = faults if faults is not None else faults_lib.active()
        self.tracer = tracer
        self.hooks = dict(hooks or {})
        self.max_rank = int(resolve_flag("DS_LORA_MAX_RANK", max_rank))
        self.rank_block = int(resolve_flag("DS_LORA_RANK_BLOCK", rank_block))
        if self.max_rank < 1 or self.rank_block < 1:
            raise ValueError("max_rank and rank_block must be >= 1")
        # static per-slot adapter-table width (row of pool block ids,
        # zero-padded; the all-zeros row is the base-only slot)
        self.blocks_per_adapter = math.ceil(self.max_rank / self.rank_block)

        # per-target shapes off the base kernels (int8-served bases
        # carry "q" with the kernel's shape); targets the model dialect
        # lacks (mlp_gate on gelu) are simply absent from the pool
        block = engine.params["block"]
        self._shapes: Dict[str, tuple] = {}
        for t in DEFAULT_TARGETS:
            entry = block.get(t)
            if not isinstance(entry, dict):
                continue
            kern = entry.get("kernel", entry.get("q"))
            if kern is None:
                continue
            self._shapes[t] = tuple(kern.shape)   # (L, in, out)
        if not self._shapes:
            raise ValueError("base model has no adaptable dense targets")
        self.n_layers = next(iter(self._shapes.values()))[0]
        self.dtype = engine.dtype
        itemsize = jnp.dtype(self.dtype).itemsize
        rb = self.rank_block
        self._block_bytes = sum(
            (din * rb + rb * dout) * L * itemsize
            for (L, din, dout) in self._shapes.values())

        if pool_blocks is None:
            budget = resolve_flag("DS_LORA_POOL_MB", pool_mb) * (1 << 20)
            pool_blocks = max(self.blocks_per_adapter,
                              int(budget // self._block_bytes))
        if pool_blocks < self.blocks_per_adapter:
            raise ValueError(
                f"adapter pool of {pool_blocks} blocks cannot hold one "
                f"max-rank adapter ({self.blocks_per_adapter} blocks)")
        # block 0 is the permanent all-zeros trash block (never
        # allocated): base-only table rows gather exact zeros from it
        self.num_blocks = int(pool_blocks) + 1
        self.a_pool = {t: jnp.zeros((L, self.num_blocks, din, rb),
                                    self.dtype)
                       for t, (L, din, dout) in self._shapes.items()}
        self.b_pool = {t: jnp.zeros((L, self.num_blocks, rb, dout),
                                    self.dtype)
                       for t, (L, din, dout) in self._shapes.items()}
        mesh = getattr(engine, "mesh", None)
        if mesh is not None:
            pool_sh = NamedSharding(mesh, PartitionSpec())
            self.a_pool = {t: jax.device_put(v, pool_sh)
                           for t, v in self.a_pool.items()}
            self.b_pool = {t: jax.device_put(v, pool_sh)
                           for t, v in self.b_pool.items()}

        # allocator state, paged_cache.py idioms: LIFO free list (pop()
        # yields ascending ids), refcounts, LRU clock over residents
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self._staged: Dict[str, List[Dict[str, Dict[str, np.ndarray]]]] = {}
        self._rank: Dict[str, int] = {}
        self._blocks: Dict[str, List[int]] = {}    # resident -> block ids
        self._refcount: Dict[str, int] = {}
        self._last_used: Dict[str, int] = {}
        self._tick = 0
        self.hits = 0
        self.loads = 0
        self.evictions = 0
        self._warm_load()

    # -- construction helpers -----------------------------------------
    def _zero_chunks(self):
        rb = self.rank_block
        a = {t: np.zeros((L, din, rb), np.float32)
             for t, (L, din, dout) in self._shapes.items()}
        b = {t: np.zeros((L, rb, dout), np.float32)
             for t, (L, din, dout) in self._shapes.items()}
        return a, b

    def _warm_load(self) -> None:
        """Compile the scatter program up front (a zero-write into the
        trash block) so a mid-run adapter load never compiles — the
        warm_cow/warm_host_tier precedent."""
        a, b = self._zero_chunks()
        self.a_pool, self.b_pool = _load_blocks(
            self.a_pool, self.b_pool, a, b, 0)

    # -- registry ------------------------------------------------------
    def register(self, adapter_id: str,
                 source: Union[str, Mapping[str, np.ndarray]]) -> None:
        """Stage ``source`` (an ``.npz`` path or an
        ``adapter_state_dict`` mapping, both the ``runtime/lora.py``
        export format) host-side under ``adapter_id``. Validates every
        leaf against the base kernels and folds ``lora_scale`` into B
        in fp32. No device memory moves until :meth:`acquire`."""
        if isinstance(source, str):
            with np.load(source) as data:
                flat = {k: np.asarray(data[k]) for k in data.files}
        else:
            flat = {k: np.asarray(v) for k, v in source.items()}
        per_target: Dict[str, Dict[str, np.ndarray]] = {}
        for key, val in flat.items():
            parts = key.split("/")
            if len(parts) != 3 or parts[0] != "block":
                raise ValueError(
                    f"adapter {adapter_id!r}: unexpected export key "
                    f"{key!r} (want 'block/<target>/lora_*')")
            _, target, leaf = parts
            if target not in self._shapes:
                raise ValueError(
                    f"adapter {adapter_id!r} adapts {target!r}, which "
                    f"the base model does not expose")
            per_target.setdefault(target, {})[leaf] = val
        if not per_target:
            raise ValueError(f"adapter {adapter_id!r}: empty export")

        rank = None
        for t, leaves in per_target.items():
            missing = {"lora_a", "lora_b", "lora_scale"} - set(leaves)
            if missing:
                raise ValueError(
                    f"adapter {adapter_id!r}/{t}: missing {sorted(missing)}")
            L, din, dout = self._shapes[t]
            a, b = leaves["lora_a"], leaves["lora_b"]
            r = a.shape[-1]
            if a.shape != (L, din, r) or b.shape != (L, r, dout):
                raise ValueError(
                    f"adapter {adapter_id!r}/{t}: shapes A{a.shape} "
                    f"B{b.shape} do not match base ({L}, {din}, {dout})")
            if rank is None:
                rank = r
            elif r != rank:
                raise ValueError(
                    f"adapter {adapter_id!r}: mixed ranks {rank} vs {r}")
        if rank > self.max_rank:
            raise ValueError(
                f"adapter {adapter_id!r} rank {rank} exceeds the pool's "
                f"max_rank {self.max_rank} (DS_LORA_MAX_RANK)")

        # fold scale into B once (fp32), chunk both factors into
        # rank-blocks zero-padded to rank_block; unadapted targets get
        # zero chunks so their gathered contribution is exactly +0.0
        rb = self.rank_block
        nb = math.ceil(rank / rb)
        chunks = []
        for j in range(nb):
            a_c, b_c = self._zero_chunks()
            lo, hi = j * rb, min((j + 1) * rb, rank)
            for t, leaves in per_target.items():
                scale = leaves["lora_scale"].astype(np.float32)
                a_c[t][:, :, :hi - lo] = (
                    leaves["lora_a"][:, :, lo:hi].astype(np.float32))
                b_c[t][:, :hi - lo, :] = (
                    leaves["lora_b"][:, lo:hi, :].astype(np.float32)
                    * scale[:, None, None])
            chunks.append({"a": a_c, "b": b_c})
        self._staged[adapter_id] = chunks
        self._rank[adapter_id] = int(rank)

    def registered(self) -> List[str]:
        return sorted(self._staged)

    # -- residency -----------------------------------------------------
    @property
    def active_adapters(self) -> int:
        return len(self._blocks)

    @property
    def pool_bytes(self) -> int:
        return self._block_bytes * self.num_blocks

    def resident(self, adapter_id: str) -> bool:
        return adapter_id in self._blocks

    def _evict_one(self) -> bool:
        """Evict the least-recently-used refcount-zero resident,
        returning its blocks to the free list. False when every
        resident is pinned by an in-flight request."""
        victims = [aid for aid, rc in self._refcount.items() if rc == 0]
        if not victims:
            return False
        victim = min(victims, key=lambda aid: self._last_used[aid])
        for bid in self._blocks.pop(victim):
            self._free.append(bid)
        del self._refcount[victim]
        del self._last_used[victim]
        self.evictions += 1
        hook = self.hooks.get("on_evict")
        if hook is not None:
            hook()
        return True

    def _pop_free(self) -> int:
        if not self._free:
            if not self._evict_one():
                raise AdapterLoadError(
                    "adapter pool exhausted: every resident adapter is "
                    "pinned by an in-flight request")
        return self._free.pop()

    def acquire(self, adapter_id: str) -> np.ndarray:
        """Pin ``adapter_id`` for one request and return its block-table
        row (``[blocks_per_adapter] int32``, zero-padded). Loads the
        adapter into the pool on a miss — the ``cache.adapter_load``
        fault site fires BEFORE any pool state moves, so a degraded
        load leaves the pool untouched. Raises
        :class:`AdapterLoadError` (or the injector's retryable error)
        on failure; the caller owns one :meth:`release`."""
        if adapter_id not in self._staged:
            raise AdapterLoadError(
                f"adapter {adapter_id!r} is not registered")
        self._tick += 1
        if adapter_id in self._blocks:
            self._refcount[adapter_id] += 1
            self._last_used[adapter_id] = self._tick
            self.hits += 1
            hook = self.hooks.get("on_hit")
            if hook is not None:
                hook()
            return self._row(adapter_id)
        fault = self.faults.fire("cache.adapter_load")
        if fault is not None and fault.kind == "cache_exhausted":
            raise AdapterLoadError(
                f"injected adapter-pool exhaustion loading {adapter_id!r}")
        chunks = self._staged[adapter_id]
        blocks: List[int] = []
        try:
            for _ in chunks:
                blocks.append(self._pop_free())
        except AdapterLoadError:
            self._free.extend(reversed(blocks))
            raise
        for bid, chunk in zip(blocks, chunks):
            self.a_pool, self.b_pool = _load_blocks(
                self.a_pool, self.b_pool, chunk["a"], chunk["b"], bid)
        self._blocks[adapter_id] = blocks
        self._refcount[adapter_id] = 1
        self._last_used[adapter_id] = self._tick
        self.loads += 1
        hook = self.hooks.get("on_load")
        if hook is not None:
            hook()
        if self.tracer is not None:
            self.tracer.event(
                "adapter_load", adapter=adapter_id,
                rank=self._rank[adapter_id], blocks=len(blocks),
                resident=len(self._blocks))
        return self._row(adapter_id)

    def release(self, adapter_id: str) -> None:
        """Drop one pin. Blocks stay resident (an LRU-evictable warm
        entry) until the pool needs the space."""
        rc = self._refcount.get(adapter_id)
        if rc is None or rc <= 0:
            raise ValueError(
                f"release of non-acquired adapter {adapter_id!r}")
        self._refcount[adapter_id] = rc - 1

    def _row(self, adapter_id: str) -> np.ndarray:
        row = np.zeros((self.blocks_per_adapter,), np.int32)
        blocks = self._blocks[adapter_id]
        row[:len(blocks)] = blocks
        return row

    # -- program plumbing ---------------------------------------------
    def lora_args(self, rows) -> tuple:
        """Package the pools + a slot table for the engine's ``lora=``
        kwarg: ``(a_pool, b_pool, rows)`` with ``rows`` ``[B, NBa]``
        (decode/verify) or ``[NBa]`` (one prefill slot) — traced data,
        so any adapter mix reuses the same compiled program; host rows,
        which ride in the dispatch's packed operand."""
        return (self.a_pool, self.b_pool, rows)

    def stats(self) -> Dict[str, int]:
        return {
            "registered": len(self._staged),
            "resident": len(self._blocks),
            "pool_blocks": self.num_blocks - 1,
            "free_blocks": len(self._free),
            "pool_bytes": self.pool_bytes,
            "hits": self.hits,
            "loads": self.loads,
            "evictions": self.evictions,
        }
