"""On-chip census of every Pallas kernel: compile through Mosaic, run,
compare with the in-repo reference.

CPU tests run the kernels in interpret mode, which checks neither tiling
nor VMEM; tests/test_kernels_lower_tpu.py runs the Pallas->Mosaic lowering
without a chip. This is the third leg: libtpu's compiler and the device's
arithmetic, one row per kernel and shape. A census records what each
kernel does, so a failing row is printed with the compiler's message and
the run goes on; the exit code is non-zero if any row failed.

Usage: python tools/kernel_census.py [substring ...] [--parent=DIR]
(``--parent``: a ``git archive`` of the parent commit, whose flash kernels
the "flash train shape" row then times and checks on the same inputs)
One JSON line per row, also appended to chiprun_out/kernel_census.jsonl.
Needs a TPU; one process.
"""

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

from deepspeed_tpu.ops.attention import flash as F  # noqa: E402
from deepspeed_tpu.ops.attention import paged as P  # noqa: E402
from deepspeed_tpu.utils import require_tpu  # noqa: E402

OUT = os.path.join("chiprun_out", "kernel_census.jsonl")
# Kernels take bf16 and accumulate in fp32; each reference is given the
# same values in fp32, so the error is the kernel's own: the bf16 rounding
# of its output (2^-9 relative) and of the probabilities it feeds the
# second matmul. Errors are relative to the largest reference value.
TOL = 2e-2

# (heads, kv_heads, head_dim): gpt2-1.5b, gpt2-medium, one GQA Dh=128
MODEL_SHAPES = ((25, 25, 64), (16, 16, 64), (32, 8, 128))


def _err(a, ref):
    a, ref = a.astype(jnp.float32), ref.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - ref))
                 / jnp.maximum(1.0, jnp.max(jnp.abs(ref))))


def _f32(*xs):
    return [x.astype(jnp.float32) if jnp.issubdtype(x.dtype, jnp.floating)
            else x for x in xs]


def _rand(r, shape, dtype=jnp.bfloat16):
    return jnp.asarray(r.standard_normal(shape), dtype)


def flash_rows():
    """Forward + all three gradients of flash_attention vs mha_reference,
    at the model shapes and over the feature matrix (mask, segments,
    windows, backward tiles)."""
    r = np.random.default_rng(0)
    S = 1024
    mask = jnp.asarray((r.random((2, S)) > 0.2).astype(np.float32))
    segs = jnp.asarray(np.repeat(np.arange(4), S // 4)[None].repeat(2, 0),
                       jnp.int32)
    cases = [(f"flash H{H}/{Hkv} D{D} blk{blk}", (H, Hkv, D), blk, {})
             for (H, Hkv, D), blk in zip(MODEL_SHAPES, (1024, 1024, 512))]
    small = (8, 8, 64)
    cases += [
        ("flash kv_mask", small, 256, {"kv_mask": mask}),
        ("flash segments", small, 256, {"segment_ids": segs}),
        ("flash window banded", small, 256, {"window": 256}),
        ("flash window banded GQA+segments", (8, 2, 64), 256,
         {"window": 256, "segment_ids": segs}),
        ("flash window banded H25 W300", (25, 25, 64), 512,
         {"window": 300}),
        ("flash bwd tiles 128", small, 256,
         {"bwd_block_q": 128, "bwd_block_kv": 128}),
    ]
    for name, (H, Hkv, D), blk, kw in cases:
        def run(H=H, Hkv=Hkv, D=D, blk=blk, kw=kw):
            q = _rand(r, (2, S, H, D))
            k, v = _rand(r, (2, S, Hkv, D)), _rand(r, (2, S, Hkv, D))
            ref_kw = {a: b for a, b in kw.items()
                      if a in ("kv_mask", "segment_ids", "window")}

            def fl(q, k, v):
                return F.flash_attention(q, k, v, causal=True, block_q=blk,
                                         block_kv=blk, **kw)

            def rf(q, k, v):
                return F.mha_reference(q, k, v, causal=True, **ref_kw)

            def sq(f):
                return lambda q, k, v: (f(q, k, v).astype(jnp.float32)
                                        ** 2).sum()
            out = jax.jit(fl)(q, k, v)
            g = jax.jit(jax.grad(sq(fl), argnums=(0, 1, 2)))(q, k, v)
            gr = jax.jit(jax.grad(sq(rf), argnums=(0, 1, 2)))(
                *_f32(q, k, v))
            fwd = _err(out, jax.jit(rf)(*_f32(q, k, v)))
            bwd = max(_err(a, b) for a, b in zip(g, gr))
            return {"fwd_err": fwd, "bwd_err": bwd,
                    "ok": fwd < TOL and bwd < TOL}
        yield name, run


# GPT-2 XL's training call, in the kernels' own layout [B, H, S, D]: one
# 1,024 x 1,024 grid block a head (benchmark/configs/gpt2-xl-train-*.json)
FLASH_TRAIN = (16, 25, 1024, 64, 1024)
FLASH_REPS = 20
# the sub-tile sides "flash train sub-tile" forces (F.SUB_TILE); 1,024 is
# no second sub-tile: the single product a grid step
FLASH_SUB_TILES = (128, 256, 512, 1024)


def _parent_flash():
    """The flash module of the tree that ``--parent=DIR`` names (a
    ``git archive`` of the parent commit), or None."""
    import importlib.util
    root = next((a.split("=", 1)[1] for a in sys.argv[1:]
                 if a.startswith("--parent=")), None)
    if root is None:
        return None
    spec = importlib.util.spec_from_file_location(
        "parent_flash", os.path.join(
            root, "deepspeed_tpu", "ops", "attention", "flash.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _flash_train_calls(M, blk, scale):
    """The three kernels of module ``M`` alone, as jitted programs of
    FLASH_REPS calls each chained through its first output (XLA drops the
    call whose results nothing reads, so ``dq`` and ``dkv`` are timed
    apart), and one forward + backward."""
    def fwd(q, k, v):
        return M._flash_fwd(q, k, v, None, None, None, True, scale, blk,
                            blk)

    def bwd(q, k, v, o, lse, do):
        return M._flash_bwd(True, scale, blk, blk, None,
                            (q, k, v, None, None, None, o, lse), do)

    def chain(f):
        def many(x, *rest):
            return jax.lax.fori_loop(
                0, FLASH_REPS, lambda _, x: f(x, *rest).astype(x.dtype), x)
        return jax.jit(many)
    return {
        "fwd": chain(lambda q, k, v: fwd(q, k, v)[0]),
        "dq": chain(lambda do, q, k, v, o, lse:
                    bwd(q, k, v, o, lse, do)[0]),
        "dkv": chain(lambda do, q, k, v, o, lse:
                     sum(bwd(q, k, v, o, lse, do)[1:])),
    }, jax.jit(fwd), jax.jit(bwd)


def _flash_train_read(M, q, k, v, do, ref=None):
    """us a call of each kernel of ``M`` at the training shape and, with
    ``ref`` (the float32 reference's o, dq, dk, dv), each output's error."""
    blk = FLASH_TRAIN[-1]
    scale = 1.0 / np.sqrt(q.shape[-1])
    timed, fwd, bwd = _flash_train_calls(M, blk, scale)
    o, lse = fwd(q, k, v)
    grads = bwd(q, k, v, o, lse, do)
    row = {}
    if ref is not None:
        row = {f"{n}_err": _err(a, b)
               for n, a, b in zip(("o", "dq", "dk", "dv"), (o,) + grads, ref)}
    row["us_fwd"] = _best_seconds(timed["fwd"], q, k, v) / FLASH_REPS * 1e6
    for n in ("dq", "dkv"):
        row[f"us_{n}"] = _best_seconds(
            timed[n], do, q, k, v, o, lse) / FLASH_REPS * 1e6
    return {a: round(b, 6 if a.endswith("err") else 1)
            for a, b in row.items()}, (o,) + grads


def _flash_train_inputs():
    B, H, S, D, _ = FLASH_TRAIN
    ks = jax.random.split(jax.random.PRNGKey(58), 4)
    return [jax.random.normal(kk, (B, H, S, D), jnp.bfloat16) for kk in ks]


@jax.jit
def _flash_train_ref(q, k, v, do):
    """(o, dq, dk, dv) of ``mha_reference`` in float32, causal, [B, H, S, D]
    in and out."""
    def rf(q, k, v):
        def t(x):
            return x.transpose(0, 2, 1, 3)
        return t(F.mha_reference(t(q), t(k), t(v), causal=True))
    o, vjp = jax.vjp(rf, *_f32(q, k, v))
    return (o,) + vjp(do.astype(jnp.float32))


def flash_train_rows():
    """The three flash kernels at GPT-2 XL's training shape (bf16
    [16, 25, 1024, 64], one 1,024 block a head, causal): ``o``, ``dq``,
    ``dk``, ``dv`` against ``mha_reference`` in float32 with one random
    ``do``, microseconds a call of each kernel, and the sub-tile census;
    beside them the same readings with the sub-tile walk off (the single
    product a grid step) and, with ``--parent=DIR``, of the parent
    tree's module on the same inputs. ``ok``: every error of the walk
    within 1.5 times the single product's (and the parent's)."""
    def run():
        q, k, v, do = _flash_train_inputs()
        B, H, S, D, blk = FLASH_TRAIN

        ref = [jnp.concatenate(x) for x in zip(*(
            _flash_train_ref(*(a[i:i + 4] for a in (q, k, v, do)))
            for i in range(0, B, 4)))]   # four batch rows: 0.4 GB of scores
        row = {"sub_tiles": list(F.tile_census(S, S, blk, blk, True))}
        row["walk"], outs = _flash_train_read(F, q, k, v, do, ref)
        old = F.SUB_TILE
        F.SUB_TILE = blk                 # no second sub-tile in a block
        try:
            row["single"], base = _flash_train_read(F, q, k, v, do, ref)
        finally:
            F.SUB_TILE = old
        parent = _parent_flash()
        if parent is not None:
            row["parent"], base = _flash_train_read(parent, q, k, v, do,
                                                    ref)
        # the walk against the single product (the parent's, if given):
        # the same terms summed in another order
        row["walk_vs_base"] = {
            n: round(_err(a, b), 6)
            for n, a, b in zip(("o", "dq", "dk", "dv"), outs, base)}
        worst = max(
            row["walk"][n] / max(row[base][n], 1e-9)
            for base in ("single", "parent") if base in row
            for n in ("o_err", "dq_err", "dk_err", "dv_err"))
        return {**row, "worst_err_ratio": worst,
                "ok": worst <= 1.5 and max(
                    row["walk"][n] for n in row["walk"]
                    if n.endswith("err")) < TOL}
    yield "flash train shape", run

    def sweep(t):
        q, k, v, do = _flash_train_inputs()
        B, H, S, D, blk = FLASH_TRAIN
        old = F.SUB_TILE
        F.SUB_TILE = t
        try:
            return {"sub_tiles": list(F.tile_census(S, S, blk, blk, True)),
                    **_flash_train_read(F, q, k, v, do)[0], "ok": True}
        finally:
            F.SUB_TILE = old
    for t in FLASH_SUB_TILES:
        yield f"flash train sub-tile {t}", functools.partial(sweep, t)


def _stats_floor_call(stat_shape, stat_block, stat_map):
    """A kernel with the backward kernels' operands and grid at the
    training shape and an empty body: what a grid step waits for when it
    computes nothing, with the two row statistics in ``stat_shape``."""
    from jax.experimental import pallas as pl
    B, H, S, D, blk = FLASH_TRAIN
    big = pl.BlockSpec((1, 1, blk, D), lambda b, h, i, j: (b, h, i, 0))
    stat = pl.BlockSpec(stat_block, stat_map)

    def body(q, k, v, do, lse, delta, dq):
        dq[...] = jnp.zeros_like(dq)
    call = pl.pallas_call(
        body, grid=(B, H, S // blk, S // blk),
        in_specs=[big] * 4 + [stat] * 2, out_specs=big,
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16))
    x = jnp.zeros((B, H, S, D), jnp.bfloat16)
    st = jnp.zeros(stat_shape, jnp.float32)

    @jax.jit
    def many(x, st):
        return jax.lax.fori_loop(
            0, FLASH_REPS, lambda _, x: call(x, x, x, x, st, st), x)
    return _best_seconds(many, x, st) / FLASH_REPS * 1e6


def flash_stats_floor_rows():
    """Microseconds a call of an EMPTY kernel that takes what ``flash_bwd_dq``
    takes (q, k, v, do, lse, delta in; dq out) at the training shape: with
    the two row statistics as [B, H, S, 8] columns (the layout before PR
    58) and as [B, H, 1, S] rows (flash._stat_spec). The difference is
    what the column blocks' copies cost a call whatever it computes."""
    def run():
        B, H, S, D, blk = FLASH_TRAIN
        row = {
            "us_columns_of_8": round(_stats_floor_call(
                (B, H, S, 8), (1, 1, blk, 8),
                lambda b, h, i, j: (b, h, i, 0)), 1),
            "us_rows": round(_stats_floor_call(
                (B, H, 1, S), (1, 1, 1, blk),
                lambda b, h, i, j: (b, h, 0, i)), 1)}
        return {**row, "ok": row["us_rows"] < row["us_columns_of_8"]}
    yield "flash stats floor", run


def ring_block_rows():
    """The ring building blocks (static q_off, separate kv-side
    segments) vs the jnp chunked block the ring falls back to."""
    from deepspeed_tpu.ops.attention.ring import (_jnp_block_bwd,
                                                  _jnp_block_fwd)
    r = np.random.default_rng(1)
    B, S, H, D = 1, 512, 4, 64
    # every q row keeps at least one matching key: a row with none is
    # garbage by contract in both implementations
    qsegs = jnp.asarray(np.repeat(np.arange(2), S // 2)[None], jnp.int32)
    ksegs = jnp.asarray(np.repeat([0, 1], [S // 4, 3 * S // 4])[None],
                        jnp.int32)
    for name, kw in [
        ("ring block q_off", dict(q_off=S)),
        ("ring block q_off window", dict(q_off=S, window=S + 128)),
        ("ring block kv segments",
         dict(q_off=S, q_segs=qsegs, kv_segs=ksegs)),
    ]:
        def run(kw=kw):
            q, k, v = (_rand(r, (B, S, H, D)) for _ in range(3))
            do = jnp.ones_like(q)
            def fwd(q, k, v):
                return F.flash_block_fwd(q, k, v, causal=True, block_q=256,
                                         block_kv=256, **kw)

            def bwd_(q, k, v, do, o, lse):
                return F.flash_block_bwd(q, k, v, do, o, lse, causal=True,
                                         block_q=256, block_kv=256, **kw)
            o, lse = jax.jit(fwd)(q, k, v)
            grads = jax.jit(bwd_)(q, k, v, do, o, lse)
            scale = 1.0 / np.sqrt(D)
            ref_kw = dict(blk_causal=True, window=kw.get("window"),
                          q_off=kw["q_off"], scale=scale, chunk=256)
            o_ref, _ = _jnp_block_fwd(q, k, v, kw.get("q_segs"),
                                      kw.get("kv_segs"), None, **ref_kw)
            delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                            axis=-1).transpose(0, 2, 1)
            g_ref = _jnp_block_bwd(q, k, v, do, lse, delta,
                                   kw.get("q_segs"), kw.get("kv_segs"),
                                   None, **ref_kw)
            fwd = _err(o, o_ref.transpose(0, 2, 1, 3))
            bwd = max(_err(a, b.transpose(0, 2, 1, 3))
                      for a, b in zip(grads, g_ref))
            return {"fwd_err": fwd, "bwd_err": bwd,
                    "ok": fwd < TOL and bwd < TOL}
        yield name, run


def _paged_inputs(r, B, Hkv, D, bs, nb, quant):
    """Pools ``[N, block, Hkv*D]`` (heads folded into the rows, as the
    paged cache stores them) with every slot's blocks filled, tables
    that scatter them over the pool, and lengths from one token to the
    last position."""
    from deepspeed_tpu.ops import quantizer
    N = B * nb + 1
    k, v = _rand(r, (N, bs, Hkv, D)), _rand(r, (N, bs, Hkv, D))
    tables = jnp.asarray(
        1 + r.permutation(B * nb).reshape(B, nb), jnp.int32)
    lengths = jnp.asarray(
        np.linspace(0, nb * bs - 8, B).astype(np.int32))
    def fold(a):
        return a.reshape(N, bs, Hkv * D)
    if not quant:
        return fold(k), fold(v), tables, lengths, {}
    kq, ks = quantizer.kv_requantize_blocks(k)
    vq, vs = quantizer.kv_requantize_blocks(v)
    return fold(kq), fold(vq), tables, lengths, \
        {"k_scale": ks, "v_scale": vs}


def paged_rows():
    """Paged decode and 5-token verify, bf16 and int8 pools, vs the
    gather references."""
    r = np.random.default_rng(2)
    B, bs, nb = 8, 16, 32
    for (H, Hkv, D) in MODEL_SHAPES:
        G = H // Hkv
        for quant in (False, True):
            for q_len in (1, 5):
                kind = "decode" if q_len == 1 else "verify5"
                name = (f"paged {kind}{' int8' if quant else ''} "
                        f"Hkv{Hkv} G{G} D{D}")

                def run(Hkv=Hkv, G=G, D=D, quant=quant, q_len=q_len):
                    k, v, tables, lengths, sc = _paged_inputs(
                        r, B, Hkv, D, bs, nb, quant)
                    scale = 1.0 / np.sqrt(D)
                    if q_len == 1:
                        q = _rand(r, (B, Hkv, G, D))
                        fn, ref = (P.paged_decode_attention,
                                   P.paged_decode_reference)
                    else:
                        q = _rand(r, (B, q_len, Hkv, G, D))
                        fn, ref = (P.paged_verify_attention,
                                   P.paged_verify_reference)
                    def call(*a):
                        return fn(*a, scale=scale, **sc)
                    out = jax.jit(call)(q, k, v, tables, lengths)
                    # int8 pools stay int8: the reference dequantizes
                    e = _err(out, ref(q.astype(jnp.float32), *_f32(k, v),
                                      tables, lengths, scale=scale, **sc))
                    return {"fwd_err": e, "ok": e < TOL}
                yield name, run


# (name, share of the slots that decode, tokens a decoding slot holds)
PAGED_FILLS = (("chat-like", 0.4, 290), ("docs-like", 0.8, 760),
               ("reason-like", 1.0, 1000))
# the serving cells' decode calls: (name, slots, kv heads, group, head
# size, block, table entries, window, layers that share the stacked pool,
# blocks a layer, fills). A ring table holds a window layer's last blocks
# in logical order, lengths relative to the first. The last four are the
# large-block cells', every slot decoding, at half, about and twice what a
# slot holds in its cell (a ring: half, 80% and all of it)
PAGED_CELL_SHAPES = (
    ("gpt2-xl table64", 17, 25, 1, 64, 16, 64, None, 48, 1088, PAGED_FILLS),
    ("k-exaone full table256", 48, 8, 8, 128, 16, 256, None, 2, 12288,
     PAGED_FILLS),
    ("k-exaone ring table9 w128", 48, 8, 8, 128, 16, 9, 128, 6, 432,
     PAGED_FILLS),
    ("smallthinker ring table33 w4096", 24, 4, 7, 128, 128, 33, 4096, 9, 793,
     (("half", 1.0, 2100), ("cell-like", 1.0, 3380), ("full", 1.0, 4200))),
    ("smallthinker full table128", 24, 4, 7, 128, 128, 128, None, 3, 3073,
     (("half", 1.0, 2200), ("cell-like", 1.0, 4400), ("twice", 1.0, 8800))),
    ("jamba2 table24", 192, 1, 20, 128, 512, 24, None, 2, 4097,
     (("half", 1.0, 1400), ("cell-like", 1.0, 2800), ("twice", 1.0, 5600))),
    ("zaya1 table6", 40, 2, 4, 128, 1024, 6, None, 20, 241,
     (("half", 1.0, 1200), ("cell-like", 1.0, 2400), ("twice", 1.0, 4800))))
PAGED_REPS = 20         # sweeps over the layers in one timed program
# what paged.STEP_BYTES is swept over at the four large-block shapes
PAGED_STEP_BYTES = tuple(k << 10 for k in (256, 512, 1024, 2048, 4096))


@functools.lru_cache(maxsize=None)
def _paged_layers(L, N, bs, nb, scale, window):
    """The decode program's attention, jitted: all the layers' pools
    stacked, layer l at ``tables + l*N``, the grid worked out once from
    the lengths, a loop over the layers (each call's result feeding the
    next one's queries), PAGED_REPS sweeps of it."""
    def layers(q, k, v, tables, lengths, active=None):
        plan = P.decode_plan(lengths, nb, bs, row_bytes=P.pool_row_bytes(k),
                             window=window, active=active)

        def layer(q, l):
            out = P.paged_decode_attention(
                q, k, v, tables + l * N, lengths, scale=scale,
                window=window, plan=plan)
            return (q + out).astype(q.dtype), None

        def sweep(_, q):
            return jax.lax.scan(layer, q, jnp.arange(L))[0]
        return jax.lax.fori_loop(0, PAGED_REPS, sweep, q)
    return jax.jit(layers)


@functools.partial(jax.jit, static_argnames=("scale", "window"))
def _paged_decode_jit(q, k, v, tables, lengths, active=None, *, scale,
                      window):
    plan = P.decode_plan(lengths, tables.shape[1], k.shape[1],
                         row_bytes=P.pool_row_bytes(k), window=window,
                         active=active)
    return P.paged_decode_attention(q, k, v, tables, lengths, scale=scale,
                                    window=window, plan=plan)


def _best_seconds(fn, *args):
    """The best of three runs of ``fn(*args)`` after one that compiles."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _time_layers(layers, L, *args):
    """Microseconds a call inside :func:`_paged_layers`' (or
    :func:`_mla_layers`') program."""
    return _best_seconds(layers, *args) / (PAGED_REPS * L) * 1e6


def _paged_time_row(B, Hkv, G, D, bs, nb, window, L, N, fills):
    """One shape's row of :func:`paged_time_rows` under the paged.STEP_BYTES
    of the moment."""
    # made on the chip: a cell's pools are gigabytes
    kk, kv, kq = jax.random.split(jax.random.PRNGKey(5), 3)
    k = jax.random.normal(kk, (L * N, bs, Hkv * D), jnp.bfloat16)
    v = jax.random.normal(kv, (L * N, bs, Hkv * D), jnp.bfloat16)
    q = jax.random.normal(kq, (B, Hkv, G, D), jnp.bfloat16)
    scale = 1.0 / np.sqrt(D)
    row_bytes = P.pool_row_bytes(k)
    per_step = P.blocks_per_step(nb, bs, row_bytes)
    # every slot its own blocks, block 0 the trash block
    tables = jnp.asarray(
        1 + (np.arange(B * nb) % (N - 1)).reshape(B, nb), jnp.int32)
    layers = _paged_layers(L, N, bs, nb, scale, window)
    cases = []
    for fill, share, tokens in fills:
        live = np.arange(B) < round(share * B)
        held = min(tokens, nb * bs - 1)
        if window is not None:      # relative to the ring's start
            held = min(tokens, (nb - 1) * bs + tokens % bs)
        lengths = np.where(live, held, 0)
        cases.append((fill, lengths,
                      int(np.sum(np.where(live, held // bs + 1, 1))),
                      int(np.sum(P.tiles_run(lengths, nb, bs, window,
                                             row_bytes=row_bytes)))))
    # jitted: called eagerly, each of the kernel's views of a
    # pool would be a program argument of the pool's size
    first = jnp.asarray(cases[0][1], jnp.int32)
    out = _paged_decode_jit(q, k, v, tables, first, scale=scale,
                            window=window)
    row = {"fwd_err": _err(out, P.paged_decode_reference(
        *_f32(q, k[:N], v[:N]), tables, first, scale=scale,
        window=window))}
    by_block, by_step = [], []
    for fill, lengths, blocks, steps in cases:
        us = _time_layers(layers, L, q, k, v, tables,
                          jnp.asarray(lengths, jnp.int32))
        row.update({f"us_{fill}": round(us, 1), f"blocks_{fill}": blocks,
                    f"steps_{fill}": steps,
                    f"us_step_{fill}": round(us / steps, 3)})
        by_block.append((blocks, us))
        by_step.append((steps, us))
    slope, fixed = np.polyfit(*zip(*by_block), 1)
    row.update(us_fixed=round(float(fixed), 1),
               us_per_block=round(float(slope), 3))
    if len({steps for steps, _ in by_step}) > 1:
        # what one more step costs, beside what its bytes alone would
        row["us_per_step"] = round(float(np.polyfit(*zip(*by_step), 1)[0]), 3)
    bytes_step = per_step * 2 * bs * row_bytes
    return {**row, "blocks_per_step": per_step, "bytes_step": bytes_step,
            "us_step_bytes": round(bytes_step / 819e9 * 1e6, 3),
            "grid_steps": B * -(-nb // per_step),
            "ok": row["fwd_err"] < TOL}


def paged_time_rows():
    """Microseconds a ``paged_decode`` call at the serving cells' shapes
    and three fills each, timed as the decode program runs it
    (:func:`_paged_layers`), and the fits a reader needs: a fixed cost a
    call and a cost per occupied block; the steps a call takes, what a
    step fetches at most (``bytes_step``; ``us_step_bytes`` at the chip's
    bandwidth) and what a step takes (``us_step_<fill>``, and
    ``us_per_step`` as the fills' slope), so that ``bytes / bandwidth +
    fixed`` is read off one table. Checked against the gather reference at
    the first fill."""
    for name, *shape in PAGED_CELL_SHAPES:
        yield (f"paged decode time {name}",
               functools.partial(_paged_time_row, *shape))


def paged_tile_rows():
    """The rows of :func:`paged_time_rows` at the four large-block shapes
    with ``paged.STEP_BYTES`` swept (``PAGED_STEP_BYTES``): the census
    behind the module's constant. A traced program read the constant when
    it was traced, so every cache of one is dropped on both edges."""
    def set_step_bytes(n):
        P.STEP_BYTES = n
        _paged_layers.cache_clear()
        jax.clear_caches()

    def run(step_bytes, shape):
        old = P.STEP_BYTES
        set_step_bytes(step_bytes)
        try:
            return {"step_bytes": step_bytes, **_paged_time_row(*shape)}
        finally:
            set_step_bytes(old)
    for name, *shape in PAGED_CELL_SHAPES[3:]:
        for step_bytes in PAGED_STEP_BYTES:
            yield (f"paged decode tile {name} step {step_bytes >> 10}KiB",
                   functools.partial(run, step_bytes, shape))


# the GPT-2 XL cells' decode dispatches as the scheduler leaves the
# slots: (name, slots that decode, tokens each holds, slots in prefill,
# tokens each has prefilled so far); the rest hold no request
PAGED_MASKED = (("chat 3 live of 17", 3, 290, 0, 0),
                ("docs 14 live 3 in prefill at 370", 14, 760, 3, 370),
                ("no slot live", 0, 0, 3, 370))


def paged_masked_time_rows():
    """Microseconds a ``paged_decode`` call at the GPT-2 XL cells' shape
    with the work list cut from the slots that decode, against the list of
    every slot (the plan before PR 36). Checked with NaN in the trash
    block and in the prefilling slots' occupied blocks: the live rows are the
    unmasked call's to the bit, the others exactly zero."""
    r = np.random.default_rng(6)
    _, B, Hkv, G, D, bs, nb, window, L, N, _ = PAGED_CELL_SHAPES[0]
    for name, n_live, held, n_pre, done in PAGED_MASKED:
        def run(n_live=n_live, held=held, n_pre=n_pre, done=done):
            k = _rand(r, (L * N, bs, Hkv * D))
            v = _rand(r, (L * N, bs, Hkv * D))
            q = _rand(r, (B, Hkv, G, D))
            scale = 1.0 / np.sqrt(D)
            slots = np.arange(B)
            live = slots < n_live
            pre = (slots >= n_live) & (slots < n_live + n_pre)
            lengths = np.where(live, held, np.where(pre, done, 0))
            tables = 1 + (np.arange(B * nb) % (N - 1)).reshape(B, nb)
            tables[~(live | pre)] = 0        # no request: the trash block
            args = (q, k, v, jnp.asarray(tables, jnp.int32),
                    jnp.asarray(lengths, jnp.int32))
            active = jnp.asarray(live)
            layers = _paged_layers(L, N, bs, nb, scale, window)
            row = {"us_every_slot": round(_time_layers(layers, L, *args), 1),
                   "us_live_slots": round(
                       _time_layers(layers, L, *args, active), 1)}
            tiles = P.tiles_run(lengths, nb, bs, window,
                                row_bytes=P.pool_row_bytes(k))
            row["steps_every_slot"] = int(tiles.sum())
            row["steps_live_slots"] = int(np.dot(tiles, live))
            # one layer's pools: a poisoned copy of all 48 would not fit
            bad = np.concatenate(
                [[0], tables[pre][:, :done // bs + 1].ravel()])
            k0, v0 = k[:N], v[:N]
            want = np.asarray(_paged_decode_jit(
                q, k0, v0, *args[3:], scale=scale, window=window), np.float32)
            got = np.asarray(_paged_decode_jit(
                q, k0.at[bad].set(jnp.nan), v0.at[bad].set(jnp.nan),
                *args[3:], active, scale=scale, window=window), np.float32)
            row["live_rows_equal"] = bool((got[live] == want[live]).all())
            row["other_rows_zero"] = bool((got[~live] == 0).all())
            return {**row, "ok": row["live_rows_equal"]
                    and row["other_rows_zero"]}
        yield f"paged decode time masked {name}", run


# the latent-attention cell's decode shape: (slots, heads, row lanes,
# value lanes, layers, tokens a slot can reach); block = tile sizes tried
MLA_SHAPE = (16, 128, 640, 512, 6, 24576)
MLA_BLOCKS = (256, 512, 1024)
MLA_FILLS = (("short", 2048), ("mean", 8704), ("long", 22016))


@functools.lru_cache(maxsize=None)
def _mla_layers(L, N, bs, nb, vw, scale):
    """The latent decode program's attention, jitted: layer l's rows at
    ``tables + l*N``, the grid worked out once, PAGED_REPS sweeps over the
    layers (:func:`_paged_layers`' shape)."""
    from deepspeed_tpu.ops.attention import mla

    def layers(q, pool, tables, lengths):
        plan = P.decode_plan(lengths, nb, bs)

        def layer(q, l):
            out = mla.mla_decode_attention(
                q, pool, tables + l * N, lengths, value_width=vw,
                scale=scale, plan=plan)
            return q.at[..., :vw].add(out).astype(q.dtype), None

        def sweep(_, q):
            return jax.lax.scan(layer, q, jnp.arange(L))[0]
        return jax.lax.fori_loop(0, PAGED_REPS, sweep, q)
    return jax.jit(layers)


@functools.partial(jax.jit, static_argnames=("vw", "scale", "kernel"))
def _mla_decode_jit(q, pool, tables, lengths, *, vw, scale, kernel):
    from deepspeed_tpu.ops.attention import mla
    fn = mla.mla_decode_attention if kernel else mla.mla_decode_reference
    return fn(q, pool, tables, lengths, value_width=vw, scale=scale)


def mla_time_rows():
    """Microseconds an ``mla_decode`` call (ops/attention/mla.py) at the
    latent-attention cell's shape, per block size (a grid step attends one
    block of 256 or more tokens) and fill, timed as the decode program
    runs it (:func:`_mla_layers`), beside the least the call could take by
    either side of its roofline. Checked against the plain latent decode
    at ragged lengths."""
    r = np.random.default_rng(7)
    B, H, row, vw, L, cap = MLA_SHAPE
    scale = 192 ** -0.5
    for bs in MLA_BLOCKS:
        def run(bs=bs):
            nb = cap // bs
            N = 1 + B * nb
            pool = _rand(r, (L * N, bs, row))
            q = _rand(r, (B, H, row))
            tables = jnp.asarray(
                1 + np.arange(B * nb).reshape(B, nb), jnp.int32)
            layers = _mla_layers(L, N, bs, nb, vw, scale)
            ragged = jnp.asarray(
                [0, cap - 1, 3, bs, bs - 1] + [1000 + 777 * i
                                               for i in range(B - 5)],
                jnp.int32)
            got = _mla_decode_jit(q, pool[:N], tables, ragged, vw=vw,
                                  scale=scale, kernel=True)
            want = _mla_decode_jit(*_f32(q, pool[:N]), tables, ragged,
                                   vw=vw, scale=scale, kernel=False)
            row_ = {"block": bs, "fwd_err": _err(got, want)}
            for fill, tokens in MLA_FILLS:
                us = _time_layers(layers, L, q, pool, tables,
                                  jnp.full((B,), tokens, jnp.int32))
                rows = B * (tokens + 1)
                least = max(2.0 * rows * H * (2 * 512 + 64) / 197e12,
                            rows * 576 * 2 / 819e9) * 1e6
                row_[f"us_{fill}"] = round(us, 1)
                row_[f"roofline_{fill}"] = round(100 * least / us, 1)
            return {**row_, "ok": row_["fwd_err"] < TOL}
        yield f"mla decode time block {bs}", run


# the latent prefill's two callers: (name, heads, model width, query rank
# (None: one projection), rotated, table entries a slot), and the histories
# (tokens before the chunk) timed; chunk and block are 512 in both cells
MLA_PREFILL_SHAPES = (
    ("dotsvlm1 H128", 128, 7168, 1536, True, 48,
     (0, 512, 2048, 8192, 22016)),
    ("kimilinear H32", 32, 2304, None, False, 16, (0, 512, 1536)))
MLA_PREFILL_REPS = 20


@functools.lru_cache(maxsize=None)
def _mla_prefill_config(H, d, rq, rotated):
    """A cell's latent layer as its config class has it."""
    from deepspeed_tpu.models import dots_vlm, kimi_linear
    kw = dict(vocab_size=256, n_heads=H, d_model=d, d_ff=256,
              max_seq_len=24576, dtype=jnp.bfloat16, moe_d_ff=256,
              use_flash_attention=False)
    if rotated:
        return dots_vlm.DotsVLMConfig(n_layers=2, q_lora_rank=rq, **kw)
    return kimi_linear.KimiLinearConfig(
        n_layers=2, kda_layers=(1,), full_attn_layers=(2,), **kw)


def _mla_prefill_weights(cfg):
    """The layer's bf16 weights at the cell's widths."""
    r = np.random.default_rng(11)
    H, d, rq = cfg.n_heads, cfg.d_model, cfg.q_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rkv = cfg.kv_lora_rank

    def w(*shape):
        return {"kernel": _rand(r, shape) * shape[-2] ** -0.5}
    p = {"ln1": {"scale": jnp.ones((d,), jnp.bfloat16)},
         "kv_a": w(d, rkv + dr),
         "kv_a_norm": {"scale": jnp.ones((rkv,), jnp.bfloat16)},
         "k_up": w(H, dn, rkv), "v_up": w(H, rkv, dv),
         "attn_out": w(H * dv, d)}
    if rq:
        p.update(q_a=w(d, rq), q_b=w(rq, H * (dn + dr)),
                 q_a_norm={"scale": jnp.ones((rq,), jnp.bfloat16)})
    else:
        p["q"] = w(d, H * (dn + dr))
    return p


@functools.lru_cache(maxsize=None)
def _mla_prefill_program(shape, impl, reps):
    """``reps`` calls of ``attend_prefill`` over one chunk at ``start``,
    jitted: each call's output the next one's input."""
    from deepspeed_tpu.inference import latent
    cfg = _mla_prefill_config(*shape)

    def program(x, pool, table, start, p):
        C = x.shape[0]

        def one(_, x):
            y, _ = latent.attend_prefill(
                x, pool, table, start + jnp.arange(C), C, p, cfg,
                jnp.int32(0), impl)
            return y.astype(x.dtype)
        return jax.lax.fori_loop(0, reps, one, x)
    return jax.jit(program)


def mla_prefill_time_rows():
    """Milliseconds a latent layer's attention sublayer takes over one
    512-token prefill chunk (inference/latent.py ``attend_prefill``: the
    projections, the write, the chunk's own tile and every history tile),
    plain and through the ``mla_prefill`` kernel, at both cells' widths and
    a row of histories; ``us_tile``: what one more history tile costs
    between the longest history and none. The kernel path is checked
    against the plain one at the second history."""
    from deepspeed_tpu.inference import latent
    C = bs = 512
    for name, H, d, rq, rotated, nb, starts in MLA_PREFILL_SHAPES:
        def run(shape=(H, d, rq, rotated), nb=nb, starts=starts):
            cfg = _mla_prefill_config(*shape)
            p = _mla_prefill_weights(cfg)
            r = np.random.default_rng(13)
            x = _rand(r, (C, shape[1]))
            pool = _rand(r, (1 + nb, bs, cfg.latent_lanes)) * 0.5
            table = jnp.arange(1, nb + 1, dtype=jnp.int32)
            row, one = {}, []
            for impl, tag in (("gather", "plain"), ("pallas", "kernel")):
                reps = _mla_prefill_program(shape, impl, MLA_PREFILL_REPS)
                ms = []
                for start in starts:
                    args = (x, pool, table, jnp.int32(start), p)
                    reps(*args).block_until_ready()
                    best = float("inf")
                    for _ in range(3):
                        t0 = time.perf_counter()
                        reps(*args).block_until_ready()
                        best = min(best, time.perf_counter() - t0)
                    ms.append(round(best / MLA_PREFILL_REPS * 1e3, 3))
                row[f"ms_{tag}"] = ms
                row[f"us_tile_{tag}"] = round(
                    (ms[-1] - ms[0]) * 1e3 / (starts[-1] // bs), 1)
                one.append(_mla_prefill_program(shape, impl, 1)(
                    x, pool, table, jnp.int32(starts[1]), p))
            row.update(starts=list(starts), fwd_err=_err(one[1], one[0]),
                       blocks_per_call=latent.blocks_per_call(cfg, bs, 2))
            return {**row, "ok": row["fwd_err"] < TOL}
        yield f"mla prefill step time {name}", run


GDN_CHUNK_REPS = 18     # a qwen3next chunk's linear layers, in turn


def gdn_chunk_time_rows():
    """Milliseconds ONE ``gdn_chunk`` call takes (ops/attention/kda.py: the
    scalar-decay chunk form) over a 512-token prefill chunk at Qwen3-Next's
    32 value heads on 16 key heads, all of 128, as plain XLA and as the
    Mosaic kernel (``ms_kernel_own_keys``: fed a key head a value head),
    and the kernel with other numbers of heads a grid step; both forms
    against the token recurrence under decays that overflow ``exp(-G)``
    (``err_*``: over the largest output, and the state's)."""
    from deepspeed_tpu.ops.attention import kda
    T, H, Hk, D = 512, 32, 16, 128

    def inputs():
        ks = jax.random.split(jax.random.PRNGKey(7), 6)

        def unit(x):
            return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
        q = unit(jax.nn.silu(jax.random.normal(ks[0], (T, Hk, D)))) \
            * D ** -0.5
        k = unit(jax.nn.silu(jax.random.normal(ks[1], (T, Hk, D))))
        v = jax.nn.silu(jax.random.normal(ks[2], (T, H, D)))
        g = -jnp.exp(jax.random.uniform(ks[3], (T, H), minval=-6.0,
                                        maxval=2.5))
        b = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
        return q, k, v, g, b, jax.random.normal(ks[5], (H, D, D))

    def program(impl):
        def many(q, k, v, g, b, s):
            def one(_, c):
                o, s = kda.gdn_chunk(q, k, c[0], g, b, c[1], impl=impl)
                return v + 1e-3 * o, s
            return jax.lax.fori_loop(0, GDN_CHUNK_REPS, one, (v, s))
        return jax.jit(many)

    def once(impl):
        return jax.jit(functools.partial(kda.gdn_chunk, impl=impl))

    def run():
        args = inputs()
        each = tuple(kda.per_value_head(a, H) for a in args[:2]) + args[2:]
        want = jax.jit(kda.gdn_recurrence)(*each)
        row = {"ms_kernel_own_keys": round(
            _best_seconds(program("pallas"), *each) / GDN_CHUNK_REPS * 1e3,
            4)}
        for impl, tag in (("gather", "xla"), ("pallas", "kernel")):
            row[f"ms_{tag}"] = round(_best_seconds(program(impl), *args)
                                     / GDN_CHUNK_REPS * 1e3, 4)
            got = once(impl)(*args)
            row[f"err_{tag}"] = max(_err(a, w) for a, w in zip(got, want))
        return {**row, "ok": max(row["err_xla"], row["err_kernel"]) < 1e-4}
    yield "gdn chunk time qwen3next", run

    def sweep():
        args, row, was = inputs(), {}, kda.CHUNK_HEADS
        try:
            for hb in (2, 4, 8, 16):
                kda.CHUNK_HEADS = hb
                row[f"ms_heads_{hb}"] = round(
                    _best_seconds(program("pallas"), *args)
                    / GDN_CHUNK_REPS * 1e3, 4)
        finally:
            kda.CHUNK_HEADS = was
        return {**row, "ok": True}
    yield "gdn chunk heads a step qwen3next", sweep


# the four sparse cells' expert layers: (cell, d, f, experts held, experts
# routed over, experts a token, sparse layers stacked behind the ``layer``
# index, tokens of a decode dispatch, tokens of a prefill chunk, the tiles
# to time for a dimension that the preferred tile does not divide)
GROUPED_SHAPES = (
    ("kexaone", 6144, 2048, 16, 128, 8, 7, 48, 256, ()),
    ("dotsvlm1", 7168, 2048, 16, 256, 8, 5, 16, 512, ()),
    ("zaya1", 2048, 2048, 16, 16, 1, 20, 40, 512, ()),
    ("kimilinear", 2304, 1024, 16, 256, 8, 26, 40, 512,
     (1024, 768, 1152, 2304)))
GROUPED_REPS = 104      # calls in one timed program, the layers in turn


def grouped_time_rows():
    """Microseconds a ``held_experts_ffn`` call takes (moe/expert_share.py:
    the sort, the gather, the group metadata, three ``gmm`` products
    (ops/grouped_matmul.py), the un-sort and the weighted sum; the scope
    ``moe_experts``) at the four sparse cells' expert shapes, for a decode
    dispatch's tokens and a prefill chunk's, every sparse layer's experts
    stacked behind the ``layer`` index as the serving programs hand them
    over. ``tiling_up`` / ``tiling_down`` are what ``grouped_tiling`` hands
    the kernel for ``wg`` / ``wi`` ``[d, f]`` and ``wo`` ``[f, d]``,
    ``ragged_tile_share`` the share of the fetched tile area outside the
    matrix, ``us_up`` / ``us_down`` one product alone (its own metadata and
    the kernel), ``least_us`` the touched experts' weights over 819 GB/s.
    Where a cell lists tiles, each is also timed FORCED on the dimension
    the preferred tile does not divide (1024 there is ``min(preferred,
    dim)``: what every product was handed until PR 44). The kernel path is
    checked
    against ``ragged_dot``."""
    for (cell, *shape, t_dec, t_chunk, forced) in GROUPED_SHAPES:
        for phase, T in (("decode", t_dec), ("chunk", t_chunk)):
            for tile in (None,) + forced:
                tag = "" if tile is None else f" forced {tile}"
                yield (f"grouped product time {cell} {phase} T{T}{tag}",
                       functools.partial(_grouped_time_row, *shape, T, tile))


@functools.lru_cache(maxsize=None)
def _grouped_programs(held, L, tile, form="own"):
    """The jitted programs of one :func:`grouped_time_rows` row. ``tile``
    and ``form`` are only keys: a program traced under one forced tiling,
    or under the parent's grouped product (:func:`moe_experts_rows`), is
    not another's."""
    from deepspeed_tpu.moe import expert_share as ES

    def stack(a):
        return jnp.broadcast_to(a[None], (L,) + a.shape).reshape(
            (-1,) + a.shape[1:])

    def ffn(h, experts, sel, w):
        def call(i, h):
            y, _ = ES.held_experts_ffn(h, experts, sel, w, (0, held), "gmm",
                                       layer=i % L)
            return h + y * jnp.asarray(1e-3, h.dtype)
        return jax.lax.fori_loop(0, GROUPED_REPS, call, h)

    def product(x, kernel, groups):
        def call(i, x):
            y = ES._grouped(x, kernel, groups, "gmm", i % L)
            return x.at[0, 0].add(y[0, 0] * jnp.asarray(1e-6, x.dtype))
        return jax.lax.fori_loop(0, GROUPED_REPS, call, x)

    def last_layer(h, experts, sel, w):
        return ES.held_experts_ffn(h, experts, sel, w, (0, held), "gmm",
                                   layer=jnp.int32(L - 1))

    def plain(h, experts, sel, w):
        return ES.held_experts_ffn(h, experts, sel, w, (0, held),
                                   "ragged_dot")
    return (jax.jit(stack), jax.jit(ffn), jax.jit(product),
            jax.jit(last_layer), jax.jit(plain))


def _grouped_inputs(r, stack, d, f, held, total, k, T):
    """One layer's held experts, the same stacked for every sparse layer
    (``stack``), ``T`` tokens, their selections over ``total`` experts and
    even weights, the held groups' sizes and the padded pair rows."""
    one = {n: {"kernel": _rand(r, (held,) + s) * s[0] ** -0.5}
           for n, s in (("wg", (d, f)), ("wi", (d, f)), ("wo", (f, d)))}
    experts = {n: {"kernel": stack(e["kernel"])} for n, e in one.items()}
    h = _rand(r, (T, d))
    sel = jnp.asarray(np.stack([r.choice(total, k, replace=False)
                                for _ in range(T)]), jnp.int32)
    w = jnp.full((T, k), 1.0 / k, jnp.float32)
    local = np.asarray(sel).reshape(-1)
    groups = jnp.asarray(np.bincount(local[local < held], minlength=held),
                         jnp.int32)
    return one, experts, h, sel, w, groups, -(-T * k // 128) * 128


def _grouped_time_row(d, f, held, total, k, L, T, tile):
    """One row of :func:`grouped_time_rows`; ``tile`` None: the rule's."""
    from deepspeed_tpu.moe import expert_share as ES
    pref = ES.GMM_TILING[1]

    def us(fn, *args):
        return round(_best_seconds(fn, *args) / GROUPED_REPS * 1e6, 2)

    def forced(a, b):
        return (ES.GMM_TILING[0], tile if a % pref else min(pref, a),
                tile if b % pref else min(pref, b))
    rule = ES.grouped_tiling
    if tile is not None:
        ES.grouped_tiling = forced
    try:
        stack, ffn, product, last_layer, plain = _grouped_programs(
            held, L, tile)
        up, down = ES.grouped_tiling(d, f), ES.grouped_tiling(f, d)
        r = np.random.default_rng(17)
        one, experts, h, sel, w, groups, M = _grouped_inputs(
            r, stack, d, f, held, total, k, T)
        got, stats = last_layer(h, experts, sel, w)
        want, _ = plain(h, one, sel, w)
        touched = int(stats[3])
        row = {"d": d, "f": f, "held": held, "experts": total, "k": k,
               "layers": L, "tokens": T, "tiling_up": list(up),
               "tiling_down": list(down),
               "ragged_tile_share": [ES.ragged_tile_share(d, f, up),
                                     ES.ragged_tile_share(f, d, down)],
               "pairs_held": int(stats[0]), "experts_touched": touched,
               "us_ffn": us(ffn, h, experts, sel, w),
               "us_up": us(product, _rand(r, (M, d)),
                           experts["wg"]["kernel"], groups),
               "us_down": us(product, _rand(r, (M, f)),
                             experts["wo"]["kernel"], groups),
               "least_us": round(touched * 3 * d * f * 2 / 819e9 * 1e6, 2),
               "fwd_err": _err(got, want)}
        return {**row, "ok": row["fwd_err"] < TOL}
    finally:
        ES.grouped_tiling = rule


# three sparse cells' expert layers, smallest experts first: (cell, d, f,
# experts held, experts routed over, experts a token, sparse layers stacked
# behind the ``layer`` index, tokens of a decode dispatch, tokens of a
# prefill chunk). Groups of the stack: 768, 416, 112
MOE_SHAPES = (
    ("qwen3next", 2048, 512, 32, 512, 10, 24, 48, 512),
    ("kimilinear", 2304, 1024, 16, 256, 8, 26, 40, 512),
    ("kexaone", 6144, 2048, 16, 128, 8, 7, 48, 256))


def _parent_grouped(x, w, sizes, impl, layer=None, metadata=None):
    """``expert_share._grouped`` as it was until PR 57: the layer's sizes
    written into a zero vector over every group of the stack, and
    megablox's ``gmm``, which makes its metadata inside, over all of
    them."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    from deepspeed_tpu.moe import expert_share as ES
    groups = sizes
    if layer is not None:
        groups = jax.lax.dynamic_update_slice(
            jnp.zeros((w.shape[0],), jnp.int32), sizes,
            (layer * sizes.shape[0],))
    return gmm(x, w, groups, preferred_element_type=x.dtype,
               tiling=ES.grouped_tiling(w.shape[1], w.shape[2]))


def moe_experts_rows():
    """Microseconds a ``held_experts_ffn`` call takes (the scope
    ``moe_experts``) with the PARENT's grouped product (megablox's ``gmm``
    over every sparse layer's stacked experts, its metadata made inside
    each of the three products over ``layers * held`` groups) and with this
    tree's (ONE metadata a layer over its ``held`` groups,
    ops/grouped_matmul.py, the stack read behind ``layer * held``); the
    rest of the layer (the sort, the sizes, the gather, the un-sort) is
    this tree's in both. At three cells' shapes, decode and
    prefill-chunk tokens; one product alone in both forms (``us_up``: the
    metadata and the kernel), the metadata alone (``us_metadata``: the
    parent's over the stack, this tree's over the layer), and the touched
    experts' bytes' time. The two forms' outputs are compared bit for
    bit."""
    for (cell, *shape, t_dec, t_chunk) in MOE_SHAPES:
        for phase, T in (("decode", t_dec), ("chunk", t_chunk)):
            yield (f"moe experts {cell} {phase} T{T}",
                   functools.partial(_moe_experts_row, *shape, T))


def _moe_experts_row(d, f, held, total, k, L, T):
    import importlib
    from deepspeed_tpu.moe import expert_share as ES
    from deepspeed_tpu.ops import grouped_matmul as GM
    megablox = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")

    def us(fn, *args):
        return round(_best_seconds(fn, *args) / GROUPED_REPS * 1e6, 2)

    r = np.random.default_rng(17)
    _, experts, h, sel, w, groups, M = _grouped_inputs(
        r, _grouped_programs(held, L, None)[0], d, f, held, total, k, T)
    x = _rand(r, (M, d))

    def metadata(form):
        def call(i, acc):
            sizes = jnp.roll(groups, i)     # of the loop: not hoisted
            if form == "own":
                md = GM.group_metadata(sizes, M, 128)
            else:
                lay = jax.lax.dynamic_update_slice(
                    jnp.zeros((L * held,), jnp.int32), sizes,
                    (i % L * held,))
                md = megablox.make_group_metadata(
                    group_sizes=lay, m=M, tm=128, start_group=jnp.int32(0),
                    num_nonzero_groups=L * held, visit_empty_groups=False)
            return acc + sum(jnp.sum(a) for a in jax.tree_util.tree_leaves(
                md))

        def program():
            return jax.lax.fori_loop(0, GROUPED_REPS, call, 0)
        return jax.jit(program)

    row = {"d": d, "f": f, "held": held, "stack_groups": L * held, "k": k,
           "tokens": T, "rows": M}
    out = {}
    rule = ES._grouped
    for form in ("parent", "own"):
        if form == "parent":
            ES._grouped = _parent_grouped
        try:
            _, ffn, product, last_layer, _ = _grouped_programs(
                held, L, None, form)
            out[form], stats = last_layer(h, experts, sel, w)
            row.update({
                f"us_ffn_{form}": us(ffn, h, experts, sel, w),
                f"us_up_{form}": us(product, x, experts["wg"]["kernel"],
                                    groups),
                f"us_metadata_{form}": us(metadata(form))})
        finally:
            ES._grouped = rule
    touched = int(stats[3])
    row.update({"experts_touched": touched, "pairs_held": int(stats[0]),
                "least_us": round(touched * 3 * d * f * 2 / 819e9 * 1e6, 2),
                "same_bits": bool(jnp.array_equal(out["parent"],
                                                  out["own"]))})
    return {**row, "ok": row["same_bits"]}


# the two serving configurations' dispatch shapes: (configuration, slots,
# table entries a slot (kexaone: 256 full + the ring's 9), prefill chunk,
# vocabulary as served)
DISPATCH_SHAPES = (("gpt2-xl-serve", 17, 64, 64, 50257),
                   ("k-exaone-236b-a23b-serve-ep8", 48, 265, 256, 19200))
DISPATCH_REPS = 200


def _host_us(fn, reps=DISPATCH_REPS):
    """Median microseconds of HOST time ``fn()`` takes to return; what it
    returns is waited for outside the timing, as a serving dispatch's
    enqueue does not wait for the device."""
    jax.block_until_ready(fn())
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        times.append(time.perf_counter() - t0)
        jax.block_until_ready(out)
    return round(float(np.median(times)) * 1e6, 1)


def dispatch_operand_rows():
    """What a serving dispatch pays on the host for its operands, at the
    two serving configurations' shapes (PERF.md, PR 31): each
    ``jnp.asarray`` the wrappers made per dispatch before PR 31 (eleven a
    prefill chunk, five a decode step) and the launch on the converted
    operands, against ``pack_operands`` and the launch on the one packed
    numpy buffer; and the two ``np.asarray`` pulls of the sampled tokens
    and log-probabilities against one ``jax.device_get`` of the pair. The
    programs are stand-ins that read every operand (thirty small device
    arrays in ``params``' place), so the times are the host's: argument
    handling, transfers, launch."""
    from deepspeed_tpu.inference.engine import _packed, pack_operands
    r = np.random.default_rng(7)
    for name, B, NB, C, V in DISPATCH_SHAPES:
        def run(B=B, NB=NB, C=C, V=V):
            seen = np.zeros((B, V), bool)
            i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
            tables = r.integers(1, 1000, (B, NB)).astype(np.int32)
            # (name, host value as the scheduler holds it, the dtype the
            # old wrapper converted it to, its kind in the packed buffer)
            prefill = (
                ("table_row", tables[0], i32, "i"),
                ("tokens", r.integers(0, V, C).astype(np.int32), i32, "i"),
                ("start", 128, i32, "i"), ("n_valid", C, i32, "i"),
                ("key", np.array([0, 11], np.uint32), u32, "u"),
                ("gen_count", 3, i32, "i"),
                ("temp", np.float32(0.0), f32, "f"),
                ("top_k", np.int32(0), i32, "i"),
                ("top_p", np.float32(1.0), f32, "f"),
                ("rep_pen", np.float32(1.0), f32, "f"),
                ("seen_row", seen[0], jnp.bool_, None))
            decode = (
                ("tables", tables, i32, "i"),
                ("lengths", r.integers(1, 900, B).astype(np.int32), i32, "i"),
                ("tokens", r.integers(0, V, B).astype(np.int32), i32, "i"),
                ("active", np.arange(B) % 2 == 0, jnp.bool_, "b"),
                ("gen_counts", np.arange(B, dtype=np.int32), i32, "i"))
            params = {f"w{i}": jnp.ones((8, 128), f32) for i in range(30)}
            pool = jnp.zeros((4, 8, 128), jnp.bfloat16)
            seen_dev = jax.device_put(seen)

            def programs(phase, n_out):
                def fn(params, k_pool, v_pool, *ops, scales=None, lora=None):
                    acc = sum(jnp.sum(o.astype(f32)) for o in ops) \
                        + sum(jnp.sum(w) for w in params.values())
                    lps = jnp.full((n_out,), acc, f32)
                    return lps.astype(i32), lps
                return jax.jit(fn), jax.jit(_packed(fn, f"census_{phase}"),
                                            static_argnames=("layout",))

            row = {}
            for phase, ops, n_out in (("prefill", prefill, 1),
                                      ("decode", decode, B)):
                each = {k: _host_us(lambda v=v, d=d: jnp.asarray(v, d))
                        for k, v, d, _ in ops}
                old, new = programs(phase, n_out)
                dev = [jnp.asarray(v, d) for _, v, d, _ in ops]
                parts = [(kind, v) for _, v, _, kind in ops if kind]
                if phase == "prefill":      # its row of the resident mask
                    parts.append(("row", 0))

                def old_path():
                    return old(params, pool, pool,
                               *(jnp.asarray(v, d) for _, v, d, _ in ops))

                def new_path():
                    packed, layout = pack_operands(*parts)
                    return new(params, pool, pool, packed, layout, seen_dev)

                np.testing.assert_array_equal(old_path()[1], new_path()[1])
                row.update({
                    f"us_{phase}_asarray": each,
                    f"us_{phase}_asarray_sum": round(sum(each.values()), 1),
                    f"us_{phase}_launch_converted": _host_us(
                        lambda: old(params, pool, pool, *dev)),
                    f"us_{phase}_old": _host_us(old_path),
                    f"us_{phase}_pack": _host_us(
                        lambda: pack_operands(*parts)[0]),
                    f"us_{phase}_packed": _host_us(new_path),
                    f"{phase}_packed_bytes": pack_operands(*parts)[0].nbytes})
                two, one = [], []
                for _ in range(DISPATCH_REPS):
                    for times, pull in (
                            (two, lambda o: (np.asarray(o[0]),
                                             np.asarray(o[1]))),
                            (one, jax.device_get)):
                        out = jax.block_until_ready(old(params, pool, pool,
                                                        *dev))
                        t0 = time.perf_counter()
                        pull(out)
                        times.append(time.perf_counter() - t0)
                row[f"us_{phase}_pull_two_asarray"] = round(
                    float(np.median(two)) * 1e6, 1)
                row[f"us_{phase}_pull_device_get"] = round(
                    float(np.median(one)) * 1e6, 1)
            return {**row, "ok": row["us_prefill_packed"]
                    < row["us_prefill_old"]}
        yield f"dispatch operands {name}", run


def int8_matmul_rows():
    from deepspeed_tpu.ops.int8_matmul import (fit_blocks, int8_matmul,
                                               int8_matmul_reference)
    r = np.random.default_rng(3)
    for M, K, N in ((8, 1024, 3072), (64, 1024, 4096), (256, 4096, 4096)):
        def run(M=M, K=K, N=N):
            x = _rand(r, (M, K))
            q = jnp.asarray(r.integers(-127, 128, (K, N)), jnp.int8)
            s = jnp.asarray(r.random((1, N)) * 0.01, jnp.float32)
            bk, bn = fit_blocks(K, N)
            out = int8_matmul(x, q, s, block_k=bk, block_n=bn)
            e = _err(out, int8_matmul_reference(x.astype(jnp.float32), q, s))
            return {"fwd_err": e, "ok": e < TOL}
        yield f"int8_matmul M{M} K{K} N{N}", run


def blocksparse_rows():
    from deepspeed_tpu.ops.sparse_attention import blocksparse as bsp
    from deepspeed_tpu.ops.sparse_attention.sparsity_config import \
        FixedSparsityConfig
    r = np.random.default_rng(4)
    S = 1024
    for H, D in ((16, 64), (25, 64), (8, 128)):
        def run(H=H, D=D):
            layout = np.asarray(FixedSparsityConfig(
                num_heads=H, block=128).make_layout(S))
            q, k, v = (_rand(r, (2, S, H, D)) for _ in range(3))
            def bsa(q, k, v):
                return bsp.blocksparse_attention(q, k, v, layout, causal=True)
            out = jax.jit(bsa)(q, k, v)
            e = _err(out, bsp.blocksparse_reference(
                *_f32(q, k, v), layout, causal=True))
            return {"fwd_err": e, "ok": e < TOL}
        yield f"blocksparse H{H} D{D}", run


def main():
    dev = require_tpu("kernel_census")
    wanted = [a for a in sys.argv[1:] if not a.startswith("--")]
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    failed = 0
    with open(OUT, "a") as out:
        for rows in (flash_rows, flash_train_rows, flash_stats_floor_rows,
                     ring_block_rows,
                     paged_rows,
                     paged_time_rows, paged_tile_rows,
                     paged_masked_time_rows, mla_time_rows,
                     mla_prefill_time_rows, gdn_chunk_time_rows,
                     grouped_time_rows, moe_experts_rows,
                     dispatch_operand_rows,
                     int8_matmul_rows, blocksparse_rows):
            for name, run in rows():
                if wanted and not any(w in name for w in wanted):
                    continue
                t0 = time.perf_counter()
                try:
                    row = run()
                except Exception as e:  # the census records the compiler's message and goes on; the exit code below still fails
                    row = {"ok": False,
                           "error": f"{type(e).__name__}: {e}"[:600]}
                row = {"kernel": name, "device": dev.device_kind,
                       **{k: (round(v, 5) if isinstance(v, float) else v)
                          for k, v in row.items()},
                       "seconds": round(time.perf_counter() - t0, 1)}
                failed += not row["ok"]
                line = json.dumps(row)
                print(line, flush=True)
                out.write(line + "\n")
    print(json.dumps({"census": "done", "failed": failed}))
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
