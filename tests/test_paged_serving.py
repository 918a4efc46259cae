"""Serving through the paged-attention kernel (the impl switch through
inference/engine.py and inference/serving.py; the kernel's own tests are
tests/test_paged_attention.py), and the prefill chunk's read of the
occupied part of its row.

The kernel runs in INTERPRET mode here (JAX_PLATFORMS=cpu, see
conftest.py). The gather path is the bit-reference: serving-level tests
assert token-for-token EQUALITY of the greedy stream, including across an
eviction/requeue."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import engine as engine_lib
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.hybrid import _rows, causal_band
from deepspeed_tpu.inference.paged_cache import chunk_blocks
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
from deepspeed_tpu.models import gpt
from deepspeed_tpu.ops.attention.paged import (blocks_per_step,
                                               gather_pool_blocks,
                                               resolve_decode_impl)
from deepspeed_tpu.telemetry import Telemetry

def tiny(**over):
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4, d_model=32,
                        max_seq_len=64, use_flash_attention=False,
                        remat=False, dtype=jnp.float32, **over)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def prompts_of(lengths, seed=1):
    r = np.random.default_rng(seed)
    return [r.integers(1, 128, n).astype(np.int32) for n in lengths]

def test_resolve_decode_impl(devices, monkeypatch):
    assert resolve_decode_impl("gather") == "gather"
    assert resolve_decode_impl("pallas") == "pallas"
    monkeypatch.setenv("DS_PAGED_DECODE_IMPL", "pallas")
    assert resolve_decode_impl(None) == "pallas"
    monkeypatch.delenv("DS_PAGED_DECODE_IMPL")
    assert resolve_decode_impl(None) == "gather"    # CPU default
    with pytest.raises(ValueError, match="expected 'pallas' or 'gather'"):
        resolve_decode_impl("cuda")


# ---------------------------------------------------------------------------
# serving parity: pallas stream == gather stream, token for token
# ---------------------------------------------------------------------------

def _serve(impl, cfg, params, prompts, n_new, **srv_kw):
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    srv = ServingEngine(eng, decode_impl=impl, **srv_kw)
    out = srv.run([ServeRequest(rid=i, prompt=p, max_new_tokens=n_new)
                   for i, p in enumerate(prompts)])
    return out, srv


def test_serving_parity_pallas_vs_gather(devices, pallas_interpret):
    """Greedy serving output is token-for-token identical under both
    impls — GQA + rotary + sliding window + chunked prefill all on, so
    the full feature stack flows through the kernel."""
    cfg, _ = tiny()
    cfg = dataclasses.replace(cfg, rotary_dim=4, use_wpe=False,
                              n_kv_heads=2, attn_window=10)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    prompts = prompts_of((4, 13, 7), seed=7)
    kw = dict(num_slots=2, block_size=4, num_blocks=30, prefill_chunk=4)
    ref, _ = _serve("gather", cfg, params, prompts, 8, **kw)
    out, srv = _serve("pallas", cfg, params, prompts, 8, **kw)
    assert srv.decode_impl == "pallas"
    for i in ref:
        np.testing.assert_array_equal(out[i], ref[i])
    assert srv.stats["peak_occupancy"] > 1    # batched decode really ran


@pytest.mark.parametrize("impl", ["gather", "pallas"])
def test_a_neighbour_in_prefill_changes_nothing(devices, pallas_interpret,
                                                impl):
    """A request decodes in a 4-slot engine while another is in
    mid-prefill (inactive in the decode dispatch, its progress as its
    length) and two slots hold nothing: its tokens and log-probabilities
    are those of the same request served alone, and the span says how
    many tiles the dispatch did not take."""
    from deepspeed_tpu.telemetry import Telemetry
    cfg, params = tiny()
    short, long_ = prompts_of((5, 41), seed=11)
    kw = dict(num_slots=4, block_size=4, num_blocks=40, prefill_chunk=4,
              decode_impl=impl)

    def run(prompts, telemetry=None):
        eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
        srv = ServingEngine(eng, telemetry=telemetry, **kw)
        reqs = [ServeRequest(rid=i, prompt=p, max_new_tokens=12,
                             logprobs=True) for i, p in enumerate(prompts)]
        out = srv.run(reqs)
        return out[0], list(reqs[0].out_logprobs)

    alone_tok, alone_lp = run([short])
    tel = Telemetry(sample_every=1)     # the tiles ride the sampled steps
    tok, lp = run([short, long_], tel)
    np.testing.assert_array_equal(tok, alone_tok)
    assert lp == alone_lp and len(lp) == 12
    spans = [s[5] for s in tel.tracer.spans() if s[1] == "serve.decode"
             and s[5].get("live") == 1]
    # one live slot; 2 idle slots a tile each, and the prefilling slot's
    # progress (4 to 40 tokens of a 64-token table: one tile of 16 blocks)
    assert spans and all(a["kv_steps"] == 1 for a in spans)
    assert {a["idle_tiles"] for a in spans} == {3}


def test_serving_parity_pallas_across_eviction(devices, pallas_interpret):
    """The eviction/requeue recompute path (tight pool, zero watermark)
    stays parity-exact under the pallas kernel."""
    cfg, params = tiny()
    p1, p2 = prompts_of((10, 9), seed=9)

    def run(impl):
        eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
        srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=7,
                            decode_impl=impl)
        srv.cache.watermark = 0
        out = srv.run([ServeRequest(rid="a", prompt=p1, max_new_tokens=12),
                       ServeRequest(rid="b", prompt=p2, max_new_tokens=10)])
        return out, srv.stats["evictions"]

    ref, ev_g = run("gather")
    out, ev_p = run("pallas")
    assert ev_g >= 1 and ev_p >= 1
    np.testing.assert_array_equal(out["a"], ref["a"])
    np.testing.assert_array_equal(out["b"], ref["b"])


def test_serving_engine_impl_defaults_to_engine(devices):
    """ServingEngine inherits the engine's resolved decode_impl (CPU
    default: gather) unless overridden."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    assert eng.decode_impl == "gather"
    assert ServingEngine(eng, num_slots=2).decode_impl == "gather"
    assert ServingEngine(eng, num_slots=2,
                         decode_impl="pallas").decode_impl == "pallas"
    with pytest.raises(ValueError):
        ServingEngine(eng, num_slots=2, decode_impl="nope")


# ---------------------------------------------------------------------------
# slot-capacity overflow (satellite): finish, don't clobber
# ---------------------------------------------------------------------------

def test_full_budget_slot_finished_not_overwritten(devices):
    """A decoding slot whose cache length has reached the per-slot block
    budget is FINISHED before the decode kernel runs — not preempted
    (the resume prompt is as long, it would requeue forever) and never
    allowed to clamp-write into its own last live block."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    srv = ServingEngine(eng, num_slots=2, block_size=4, num_blocks=40)
    req = ServeRequest(rid="full", prompt=prompts_of((8,))[0],
                       max_new_tokens=16)
    srv.submit(req)
    srv._admit()
    slot = srv.slots.index(req)
    # drive the slot to the edge of its block budget by hand
    srv.cache.ensure_capacity(slot, srv.cache.tokens_per_slot)
    srv.cache.lengths[slot] = srv.cache.tokens_per_slot
    req.out.append(1)
    srv._seat(slot, req, "decode")
    used_before = srv.cache.used_blocks
    assert srv._decode_step(now=0.0) == 0     # nothing decoded
    assert req.state == "done" and req in srv.finished
    assert srv.slots[slot] is None
    assert srv.cache.used_blocks < used_before   # blocks back in the pool
    assert srv.stats["evictions"] == 0


@pytest.mark.parametrize("impl", ["gather", "pallas"])
def test_engine_masks_capacity_overflow_write(devices, pallas_interpret,
                                              impl):
    """Engine-side belt: decode_slots with lengths == NB*block routes
    the new token's K/V write to the trash block instead of clamping
    into the slot's last live block."""
    cfg, params = tiny()
    eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
    bs, NB = 4, 3
    N = 8
    L, Hkv, Dh = cfg.n_layers, cfg.kv_heads, cfg.head_dim
    rng = np.random.default_rng(0)
    kp = jnp.asarray(rng.normal(size=(L, N, bs, Hkv * Dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(L, N, bs, Hkv * Dh)), jnp.float32)
    tables = np.zeros((2, NB), np.int32)
    tables[0] = [1, 2, 3]
    tables[1] = [4, 5, 6]
    # slot 0 at FULL budget, slot 1 mid-block
    lengths = np.array([NB * bs, 5], np.int32)
    active = np.array([True, True])
    _, k2, v2 = eng.decode_slots(kp.copy(), vp.copy(), tables, lengths,
                                 np.array([3, 4], np.int32), active,
                                 impl=impl)
    # every block slot 0 owns is untouched (the overflow write went to
    # each layer's trash block 0, and to no other layer's blocks);
    # slot 1's current position DID get written
    np.testing.assert_array_equal(np.asarray(k2)[:, 1:4],
                                  np.asarray(kp)[:, 1:4])
    assert not np.array_equal(np.asarray(k2)[:, 0], np.asarray(kp)[:, 0])
    np.testing.assert_array_equal(np.asarray(k2)[:, 6:],
                                  np.asarray(kp)[:, 6:])
    assert not np.array_equal(np.asarray(k2)[:, 5, 1],
                              np.asarray(kp)[:, 5, 1])
    assert not np.array_equal(np.asarray(v2)[:, 5, 1],
                              np.asarray(vp)[:, 5, 1])


# ---------------------------------------------------------------------------
# the prefill chunk's read: the occupied part of the slot's row only
# ---------------------------------------------------------------------------

def _whole_row_block(x, pools, table_row, positions, n_valid, p, cfg, lora,
                     base):
    """The plain reference: one block over a prompt chunk that writes the
    chunk's K and V, gathers the slot's WHOLE row, whatever is occupied,
    and lets the causal band mask the rest (the engine's two-pool read
    until PR 41)."""
    B, C, D = x.shape
    H, Dh, Hkv = cfg.n_heads, cfg.head_dim, cfg.kv_heads
    k_pool, v_pool = pools
    bs, NB = k_pool.shape[1], table_row.shape[0]
    lr = (lambda t: None) if lora is None else lora.get
    h = gpt._norm(x, p["ln1"], cfg)
    qkv = gpt._dense(h, p["qkv"], lora=lr("qkv"))
    q, k, v = gpt._qkv_split_rotary(qkv, cfg, positions[None], B, C)
    valid = jnp.arange(C) < n_valid
    trow = table_row + base
    blk = table_row[jnp.clip(positions // bs, 0, NB - 1)]
    blk = jnp.where(valid, blk, 0) + base
    k_pool = k_pool.at[blk, positions % bs].set(_rows(k[0]))
    v_pool = v_pool.at[blk, positions % bs].set(_rows(v[0]))
    kc = gather_pool_blocks(k_pool, trow[None], Hkv)[0]   # [NB*bs, Hkv, Dh]
    vc = gather_pool_blocks(v_pool, trow[None], Hkv)[0]
    qg = q[0].reshape(C, Hkv, H // Hkv, Dh)
    scores = jnp.einsum("ckgd,skd->ckgs", qg, kc).astype(jnp.float32)
    scores *= cfg.attn_scale if cfg.attn_scale is not None \
        else 1.0 / np.sqrt(Dh)
    sidx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, NB * bs), 3)
    scores = causal_band(scores, sidx, positions[:, None, None, None],
                         cfg.attn_window)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    attn = jnp.einsum("ckgs,skd->ckgd", probs, vc).reshape(1, C, D)
    x = x + gpt._dense(attn, p["attn_out"], lora=lr("attn_out"))
    h = gpt._norm(x, p["ln2"], cfg)
    return x + engine_lib._ffn(h, p, cfg, lora=lora), (k_pool, v_pool)


_CHUNK = 64
_READ_VARIANTS = {
    # GPT-2's own shape of attention, and everything the read must carry
    # at once: grouped KV heads, a window, a scale of its own, a LoRA row,
    # and a table whose first blocks another slot's table holds too
    "plain": dict(over={}, lora=False, shared=False),
    "gqa_window_lora_shared": dict(
        over=dict(n_kv_heads=2, attn_window=200, attn_scale=0.2),
        lora=True, shared=True),
}


@functools.lru_cache(maxsize=None)
def _read_problem(variant):
    spec = _READ_VARIANTS[variant]
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4, d_model=64,
                        max_seq_len=1024, use_flash_attention=False,
                        remat=False, dtype=jnp.float32, **spec["over"])
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    layer = 1                       # not the first: ``base`` is not 0
    p = jax.tree_util.tree_map(lambda a: a[layer], params["block"])
    bs, NB = 16, 64                 # the cells' block and table: 1,024
    N = 2 * NB + 1                  # two slots' blocks and the trash block
    r = np.random.default_rng(7)
    D, row = cfg.d_model, cfg.kv_heads * cfg.head_dim
    # stale floats in every lane no chunk has written
    pools = tuple(jnp.asarray(r.normal(size=(cfg.n_layers * N, bs, row)),
                              jnp.float32) for _ in range(2))
    ids = r.permutation(np.arange(1, N))
    other, own = ids[:NB], ids[NB:]
    lora = None
    if spec["lora"]:
        def factors(i, o, rb=4, nba=2):
            return (jnp.asarray(r.normal(size=(1, nba, i, rb)) * 0.1,
                                jnp.float32),
                    jnp.asarray(r.normal(size=(1, nba, rb, o)) * 0.1,
                                jnp.float32))
        lora = {"qkv": factors(D, cfg.qkv_dim), "attn_out": factors(D, D)}
    x = jnp.asarray(r.normal(size=(1, _CHUNK, D)), jnp.float32)

    def table_for(start):
        # the blocks below the matched boundary are the other slot's
        shared = start // bs if spec["shared"] else 0
        return jnp.asarray(np.concatenate([other[:shared], own[shared:]]),
                           jnp.int32)

    def run(block):
        def chunk(pools, table_row, start, n_valid):
            positions = start + jnp.arange(_CHUNK, dtype=jnp.int32)
            return block(x, pools, table_row, positions, n_valid, p, cfg,
                         lora=lora, base=layer * N)
        return jax.jit(chunk)
    return (pools, table_for, run(engine_lib._block_prefill_paged),
            run(_whole_row_block), other, layer * N)


@pytest.mark.parametrize("variant", sorted(_READ_VARIANTS))
@pytest.mark.parametrize("n_valid", [1, _CHUNK - 1, _CHUNK])
@pytest.mark.parametrize("start", [0, 16, 48, 64, 127, 128, 192, 512, 960])
def test_prefill_chunk_reads_the_occupied_part_of_its_row(devices, start,
                                                          n_valid, variant):
    """The dense pass over the shortest run of tiles that holds what the
    chunk's valid queries see is the whole-row softmax: the same output on
    every valid lane, and the same pools to the last bit outside the
    layer's trash block, which holds whatever came last (the reference
    scatters a row a token, the engine moves whole blocks:
    tests/test_write_chunk.py; a shared block is read, never written)."""
    pools, table_for, new, ref, other, base = _read_problem(variant)
    table_row = table_for(start)
    y, got = new(pools, table_row, start, n_valid)
    y_ref, want = ref(pools, table_row, start, n_valid)
    np.testing.assert_allclose(np.asarray(y)[0, :n_valid],
                               np.asarray(y_ref)[0, :n_valid],
                               atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(y)).all()
    for a, b, before in zip(got, want, pools):
        np.testing.assert_array_equal(np.delete(np.asarray(a), base, 0),
                                      np.delete(np.asarray(b), base, 0))
        if _READ_VARIANTS[variant]["shared"]:
            np.testing.assert_array_equal(np.asarray(a)[other + base],
                                          np.asarray(before)[other + base])


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _traced_tiles(start, n, bs, nb, window):
    return engine_lib.attended_tiles(start, n, bs, nb, window)[:2]


@pytest.mark.parametrize("bs,nb,window", [(16, 64, None), (16, 64, 200),
                                          (4, 16, None), (512, 48, None),
                                          (16, 100, 1), (16, 512, 300)])
def test_attended_tiles_cover_what_a_chunk_sees_and_little_more(bs, nb,
                                                                window):
    """The length the program picks from ``(start, n)`` and the count the
    scheduler makes of it are ONE function: every key a valid query may
    see lies in ``[lo, hi)``, the run is no longer than that needs, the
    table is cut into at most ``PREFILL_READ_LENGTHS`` tiles, and a
    traced ``start`` gives what a Python one gives."""
    attended_tiles = engine_lib.attended_tiles
    for start in range(0, nb * bs - 64, max(1, nb * bs // 97)):
        for n in (1, 63, 64):
            lo, hi, P = attended_tiles(start, n, bs, nb, window)
            W = P * bs
            assert P % blocks_per_step(nb, bs) == 0
            assert -(-nb // P) <= engine_lib.PREFILL_READ_LENGTHS
            oldest = 0 if window is None else max(start - window + 1, 0)
            assert lo * W <= oldest < (lo + 1) * W
            assert (hi - 1) * W < start + n <= hi * W
            tlo, thi = _traced_tiles(jnp.int32(start), jnp.int32(n), bs, nb,
                                     window)
            assert (int(tlo), int(thi)) == (lo, hi)


# (block, table entries, window) -> attended_tiles at a chunk of 64 tokens
# at the row's start, middle and end, as PR 53's parent returned them
PREFILL_TILES = {
    "smallthinker-ring": ((128, 33, 4096), [(0, 1, 5), (0, 4, 5), (0, 7, 5)]),
    "smallthinker-row": ((128, 128, None),
                         [(0, 1, 16), (0, 5, 16), (0, 8, 16)]),
    "kexaone-ring": ((16, 9, 128), [(0, 1, 9), (0, 1, 9), (0, 1, 9)]),
    "kexaone-row": ((16, 256, None), [(0, 1, 32), (0, 5, 32), (0, 8, 32)]),
    "gpt2-xl-row": ((16, 64, None), [(0, 1, 8), (0, 5, 8), (0, 8, 8)]),
    "jamba2-row": ((512, 24, None), [(0, 1, 3), (0, 5, 3), (0, 8, 3)]),
    "zaya1-row": ((1024, 6, None), [(0, 1, 1), (0, 4, 1), (0, 6, 1)]),
}


@pytest.mark.parametrize("cell", PREFILL_TILES)
def test_prefill_read_tiles_do_not_follow_the_decode_kernels_bytes(cell):
    """A prefill chunk's read lengths stand on the 128-position tile,
    whatever the decode kernel's step fetches since it follows its pool's
    bytes (``blocks_per_step(nb, bs, row_bytes)``: four blocks of 128 a
    step for these rows): the ring's tiles stay 640 positions and the
    row's 2,048 in smallthinker (PERF.md 6, PR 51), and no prefill
    program changes."""
    (bs, nb, window), want = PREFILL_TILES[cell]
    got = [engine_lib.attended_tiles(start, 64, bs, nb, window)
           for start in (0, nb * bs // 2, nb * bs - 64)]
    assert got == want
    assert blocks_per_step(nb, bs) == -(-128 // bs) or nb * bs <= 256


# ---------------------------------------------------------------------------
# the chunk's write: whole blocks, also from inside a copy-on-write block
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("matched,blocks", [(0, 4), (40, 5)],
                         ids=["aligned", "mid-block"])
def test_a_chunk_admitted_inside_a_block_writes_one_block_more(devices,
                                                               matched,
                                                               blocks):
    """With the prefix cache on, a request whose prompt leaves a cached one
    INSIDE a block is admitted at an unaligned ``start`` (the block is
    copied on write): its chunk of 64 in blocks of 16 has rows in 5 blocks
    where an aligned one has them in 4, the span and the counter say so,
    and it emits the tokens it emits with the cache off."""
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, n_heads=4, d_model=32,
                        max_seq_len=256, use_flash_attention=False,
                        remat=False, dtype=jnp.float32)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    donor, = prompts_of((120,), seed=3)
    later = donor.copy()
    later[matched:] = 1 + (later[matched:] + 7) % 127    # leaves it there
    kw = dict(num_slots=2, block_size=16, num_blocks=40, prefill_chunk=64)

    def serve(prefix_cache):
        eng = InferenceEngine(config=cfg, params=params, dtype=jnp.float32)
        tel = Telemetry()
        srv = ServingEngine(eng, prefix_cache=prefix_cache, telemetry=tel,
                            **kw)
        srv.run([ServeRequest(rid="donor", prompt=donor, max_new_tokens=4)])
        before = srv.stats["prefill_write_blocks_total"]
        out = srv.run([ServeRequest(rid="later", prompt=later,
                                    max_new_tokens=6)])
        spans = [r[5] for r in tel.tracer.spans()
                 if r[1] == "serve.prefill" and r[2] == "later"]
        return out["later"], spans, srv, before

    ref, cold_spans, _, _ = serve(False)
    out, spans, srv, before = serve(True)
    np.testing.assert_array_equal(out, ref)
    assert srv.cache.cow_copies == (1 if matched % 16 else 0)
    assert [(f["start"], f["n"]) for f in spans] == [
        (s, min(64, 120 - s)) for s in range(matched, 120, 64)]
    assert spans[0]["blocks"] == blocks
    assert [f["blocks"] for f in cold_spans] == [4, 4]      # 64, then 56
    nb = srv.cache.blocks_per_slot
    assert [f["blocks"] for f in spans] == [
        chunk_blocks(f["start"], f["n"], 16, nb)[1] for f in spans]
    assert srv.stats["prefill_write_blocks_total"] - before \
        == sum(f["blocks"] for f in spans)
