"""Which state a model keeps per serving slot, and which layer loop runs
it: decided HERE, once, for the engine, the cache and the scheduler.

A :class:`Dialect` is one record a cache dialect, defined in the dialect's
own module beside its blocks (``DIALECT`` of inference/linear.py, hybrid.py,
latent.py, cca.py, and of engine.py for the plain K and V pools of the GPT
blocks), and :func:`of` returns the one that owns a config. InferenceEngine
and PagedKVCache each ask once and keep the answer; nothing else asks a
module's ``is_*``. Adding a dialect: docs/PARITY.md."""

from typing import Callable, NamedTuple, Optional

import jax.numpy as jnp

from deepspeed_tpu.models.gpt import kv_bytes_per_token
from deepspeed_tpu.ops.attention.paged import decode_plan, pool_row_bytes


class SlotBytes(NamedTuple):
    """Bytes ONE serving slot holds whatever its length: a slot's share of
    PagedKVCache's ``<field>_bytes``, in its order."""
    window: int = 0
    cca_tail: int = 0
    recurrent_state: int = 0
    conv_tail: int = 0


class Dialect(NamedTuple):
    """What the engine, the cache and the scheduler read of a dialect."""
    owns: Callable            # (cfg) -> bool
    # (cfg, num_blocks, block_size, num_slots, dtype) -> (k, v), zeroed
    new_state: Callable
    pool: Callable            # (k) -> the array behind the block tables
    # every layer of a serving program -> (x, pools), handed the engine for
    # its shared pieces (_scan_layers, _dense_then_sparse, _dispatch_record):
    # (eng, params, pools, x, table_row, positions, n_valid, slot, lora)
    # and (eng, params, pools, x, tables, lengths, active, impl, lora)
    prefill_layers: Callable
    decode_layers: Callable
    # (cfg, start, n, bs, nb) -> positions of its slot's row a
    # prefill chunk reads from the pool in a layer that pages its history
    prefill_reads: Callable
    # (cfg) -> the (rule, doc) of refuse()'s message; None: refuses nothing
    refusal: Optional[Callable] = None
    # the class that rides in k's place (stats, route); None: a bare pool
    state: Optional[type] = None
    bytes_per_token: Callable = kv_bytes_per_token          # (cfg, dtype)
    slot_bytes: Callable = lambda cfg, block_size, dtype: SlotBytes()
    # entries behind the blocks of a table row
    ring_blocks: Callable = lambda cfg, block_size: 0
    # (cfg, pool) -> a pool row's bytes, by which the paged_decode kernel
    # cuts its tile (paged.blocks_per_step); None where the rows are latents
    # and mla_decode attends them in its own tile
    tile_row_bytes: Callable = lambda cfg, pool: pool_row_bytes(pool)
    # latent flash steps of a prefill chunk at start
    flash_steps: Callable = lambda cfg, start, bs: 0
    needs_slot: bool = False  # the prefill finds its state by slot index
    # registers the dialect's own gauges, by literal name (dslint DS014)
    gauges: Callable = lambda reg, cache: None
    # (cfg, impl) -> what the "inference engine ready" line says of the
    # state and of what the engine's impl chooses in the dialect's programs
    ready_note: Callable = lambda cfg, impl=None: ""


def carried_layers(block_prefill, block_decode, plan, flat, layer_bases,
                   pack, needs_slot: bool = False):
    """``prefill_layers`` and ``decode_layers`` of a dialect whose buffers
    ride in the carry of InferenceEngine._dense_then_sparse: its two blocks,
    ``plan(cfg, pools, tables, lengths, active)`` (the decode kernel's
    grid, once a dispatch), ``flat(pools) -> (buffers, stats)`` taking
    (K state, V state) apart, ``layer_bases(cfg, buffers)``, ``pack(buffers,
    stats, route)`` putting them back; ``needs_slot``: the prefill block
    takes the slot's index."""
    def layers(eng, params, pools, block, x, phase: int):
        # phase: the row of the K state's counters this program adds to
        bufs, stats = flat(pools)
        x, bufs, stats, route = eng._dense_then_sparse(
            params, bufs, layer_bases(eng.cfg, bufs), block, x, stats, phase)
        return x, pack(bufs, stats, route)

    def prefill_layers(eng, params, pools, x, table_row, positions, n_valid,
                       slot, lora):
        ops = (table_row, positions, n_valid) + ((slot,) if needs_slot else ())

        def block(carry, bufs, layer_p, base, lora, experts):
            return block_prefill(carry, bufs, *ops, layer_p, eng.cfg, base,
                                 eng.decode_impl, experts)
        return layers(eng, params, pools, block, x, 0)

    def decode_layers(eng, params, pools, x, tables, lengths, active, impl,
                      lora):
        grid = plan(eng.cfg, pools, tables, lengths, active)

        def block(carry, bufs, layer_p, base, lora, experts):
            return block_decode(carry, bufs, tables, lengths, active,
                                layer_p, eng.cfg, base, impl, experts, grid)
        return layers(eng, params, pools, block, x, 1)
    return dict(prefill_layers=prefill_layers, decode_layers=decode_layers,
                needs_slot=needs_slot)


def full_layers_kv_bytes(cfg, dtype=jnp.bfloat16) -> int:
    """K+V a token of the ``n_full_layers`` that page K and V: a window
    layer's ring and a recurrent layer's state add nothing per token."""
    return int(2 * cfg.n_full_layers * cfg.kv_heads * cfg.head_dim
               * jnp.dtype(dtype).itemsize)


def rows_plan(cfg, pools, tables, lengths, active):
    """The decode kernel's grid over a K-side state's ``rows``, in the tile
    of the kernel that attends them: the dialect says which."""
    rows = pools[0].rows
    return decode_plan(lengths, tables.shape[1], rows.shape[2],
                       row_bytes=of(cfg).tile_row_bytes(cfg, rows),
                       active=active)


def occupied_reads(cfg, start: int, n: int, bs: int, nb: int) -> int:
    """The occupied blocks of ``[0, start)`` (latent.py's, cca.py's walk)."""
    return (start + bs - 1) // bs * bs


def of(cfg) -> Dialect:
    """The one dialect that owns ``cfg``. The recurrent state is asked first
    (latent layers beside one would answer for latent.py); the plain K and
    V pools own the rest, refuse nothing and carry int8 scales and LoRA."""
    from deepspeed_tpu.inference import cca, engine, hybrid, latent, linear
    return next(d for d in (linear.DIALECT, hybrid.DIALECT, latent.DIALECT,
                            cca.DIALECT, engine.DIALECT) if d.owns(cfg))


def refuse(cfg, what: str):
    """Raise by name for a serving feature that a model whose cache state
    is more than K and V blocks cannot yet live with: the ONE refusal."""
    refusal = of(cfg).refusal
    if refusal is not None:
        rule, doc = refusal(cfg)
        raise ValueError(f"{what} is not supported for a model with "
                         f"{rule}: see docs/{doc}.md")
