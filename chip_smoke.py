"""The quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of gpt2-1.5b (48 layers, d_model 1600, 25 heads of 64) with
random weights from a seed:

- the trainer: ``deepspeed_tpu.initialize`` with bench.py's headline
  settings (bf16 memory_efficient, ZeRO stage 3, full remat, flash
  1024x1024, chunked loss, batch 16 x seq 1024) on every chip it finds,
  a few ``train_batch`` steps on one fixed batch;
- the server: ``deepspeed_tpu.init_inference`` -> ``ServingEngine`` with
  default arguments apart from sizes, a few staggered requests.

It checks what comes out (loss finite and falling, every request done in
full, kernels agreeing with their references within the tolerances below)
and reads the COMPILED programs for the Mosaic kernels. Any failed check
raises: the exit code is then non-zero and no result line is printed.
There is no CPU mode. One process; it starts no other.

Usage: python chip_smoke.py [--layers N]
Last line of stdout on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

import argparse
import gc
import json
import os
import re
import sys
import time

import jax

DEV = jax.devices()[0]
if DEV.platform != "tpu":
    sys.exit(f"chip_smoke: no TPU. JAX reports platform={DEV.platform!r} "
             f"({DEV.device_kind}); this script has no CPU mode.")

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine  # noqa: E402
from deepspeed_tpu.models import gpt  # noqa: E402
from deepspeed_tpu.ops.attention import flash, paged  # noqa: E402
from deepspeed_tpu.utils import setup_compile_cache  # noqa: E402
from deepspeed_tpu.utils.compile_guard import CompileWatch  # noqa: E402

PRESET = "gpt2-1.5b"
BATCH, SEQ = 16, 1024            # bench.py's headline
TRAIN_STEPS = 4                  # after the compiling step
# Kernels take bf16 and accumulate in fp32; the references are given the
# same values in fp32, so the error is the kernel's own. Standard-normal
# inputs: outputs reach |4|, where half a bf16 ulp is 8e-3, and the kernel
# also rounds its probabilities to bf16 before the second matmul; the
# gradients of a sum-of-squares loss reach |20|.
FWD_TOL, BWD_TOL = 2e-2, 2e-1
MOSAIC = 'custom_call_target="tpu_custom_call"'


def say(**row):
    print(json.dumps(row), flush=True)


class Recorder:
    """Stands in for one of an engine's jitted programs and keeps the
    compiled HLO of the first call. The text is taken before the call,
    while the donated arguments are still alive; lowering and executable
    are shared with the call that follows, so the program that is read is
    the program that runs, compiled once."""

    def __init__(self, jitted):
        self.jitted = jitted
        self.text = None

    def __call__(self, *args):
        if self.text is None:
            self.text = self.jitted.lower(*args).compile().as_text()
        return self.jitted(*args)


def mosaic_calls(hlo: str):
    """[(kernel name, [operand shapes])] of the Mosaic custom calls in a
    compiled program. The name is the pallas_call's ``name``, which jax
    puts in the op_name metadata."""
    out = []
    for line in hlo.splitlines():
        if MOSAIC not in line:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        ops = re.search(r"operand_layout_constraints=\{([^{}]*(?:\{[^{}]*\}"
                        r"[^{}]*)*)\}", line)
        shapes = re.findall(r"\w+\[[\d,]*\]", ops.group(1)) if ops else []
        out.append((name.group(1) if name else "?", shapes))
    return out


def _err(a, b) -> float:
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def host_params(cfg, seed=0):
    """The stacked fp32 parameter tree, built on the host with numpy
    (gpt.host_param_factory): the chip's memory then holds the training
    state only, never a second copy of the masters."""
    fac = gpt.host_param_factory(seed, cfg)
    layers = [fac(i) for i in range(cfg.n_layers)]
    params = fac("other")
    params["block"] = jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                             *layers)
    return params


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def flash_parity():
    """Flash forward and backward against mha_reference at the model's
    head geometry on a small batch."""
    r = np.random.default_rng(0)
    q, k, v = (jnp.asarray(r.standard_normal((2, SEQ, 25, 64)),
                           jnp.bfloat16) for _ in range(3))

    def fl(q, k, v):
        return flash.flash_attention(q, k, v, causal=True, block_q=1024,
                                     block_kv=1024)

    def rf(q, k, v):
        return flash.mha_reference(q, k, v, causal=True)

    def sq(f):
        return lambda q, k, v: (f(q, k, v).astype(jnp.float32) ** 2).sum()

    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    fwd = _err(jax.jit(fl)(q, k, v), jax.jit(rf)(q32, k32, v32))
    g = jax.jit(jax.grad(sq(fl), argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(sq(rf), argnums=(0, 1, 2)))(q32, k32, v32)
    bwd = max(_err(a, b) for a, b in zip(g, gr))
    say(phase="flash_parity", fwd_err=round(fwd, 5), bwd_err=round(bwd, 5),
        fwd_tol=FWD_TOL, bwd_tol=BWD_TOL)
    assert fwd < FWD_TOL and bwd < BWD_TOL, (fwd, bwd)


def train_phase(cfg, params):
    n = len(jax.devices())
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=gpt.make_loss_fn(cfg), model_parameters=params,
        config={
            "train_batch_size": BATCH,
            "bf16": {"enabled": True, "memory_efficient": True},
            "zero_optimization": {"stage": 3},
            "optimizer": {"type": "adamw",
                          "params": {"lr": 1e-4, "weight_decay": 0.1}},
            "steps_per_print": 10_000,
        })
    step = engine._train_step = Recorder(engine._train_step)
    data = {"tokens": np.random.default_rng(0).integers(
        0, cfg.vocab_size, (BATCH, SEQ + 1)).astype(np.int32)}

    t0 = time.perf_counter()
    losses = [float(engine.train_batch(data)["loss"])]
    compile_s = time.perf_counter() - t0
    times = []
    with CompileWatch(0, label="trainer steady steps"):
        for _ in range(TRAIN_STEPS):
            t0 = time.perf_counter()
            losses.append(float(engine.train_batch(data)["loss"]))
            times.append(time.perf_counter() - t0)
    say(phase="train", layers=cfg.n_layers, batch=BATCH, seq=SEQ,
        mesh=dict(zip(engine.mesh.axis_names, engine.mesh.devices.shape)),
        attention=gpt.attention_impl(cfg, SEQ),
        losses=[round(x, 4) for x in losses],
        first_step_s=round(compile_s, 1),
        steady_step_s=round(sorted(times)[len(times) // 2], 3))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"

    # the kernels, read from the compiled step
    calls = mosaic_calls(step.text)
    say(phase="train_hlo", mosaic_calls=calls)
    local_batch = BATCH // n
    for kernel in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        shapes = [s for name, s in calls if kernel in name]
        assert shapes, f"no {kernel} Mosaic call in the compiled train step"
        # q, k and v arrive as [B, H, S, D]: B is this chip's share
        want = f"bf16[{local_batch},{cfg.n_heads},{SEQ},{cfg.head_dim}]"
        assert all(s[:3] == [want] * 3 for s in shapes), (kernel, shapes)

    # where the state lives
    leaves = jax.tree_util.tree_leaves(engine.state.params) \
        + jax.tree_util.tree_leaves(engine.state.opt_state)
    big = [x for x in leaves if x.size >= 1 << 20]
    split = sum(int(np.prod(x.sharding.shard_shape(x.shape))) * n == x.size
                for x in big)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    say(phase="train_state", devices=n, big_leaves=len(big),
        split_n_ways=split,
        peak_gib_per_device=[p and round(p / 2 ** 30, 2) for p in peaks],
        limit_gib=round(((DEV.memory_stats() or {}).get("bytes_limit") or 0)
                        / 2 ** 30, 2))
    if n > 1:
        assert split == len(big), (
            f"{len(big) - split} of {len(big)} large parameter/optimizer "
            f"leaves are not split {n} ways")


def _requests(cfg, seed):
    """Six requests: prompts of a few hundred tokens (several 64-token
    prefill chunks each), 32 new tokens."""
    r = np.random.default_rng(seed)
    return [ServeRequest(rid=i, max_new_tokens=32,
                         prompt=r.integers(1, cfg.vocab_size,
                                           int(n)).astype(np.int32))
            for i, n in enumerate((200, 330, 270, 410, 150, 300))]


def _drive(srv, reqs):
    """Submit two requests, then one more every third step, so prefill
    chunks and decode steps interleave; step until idle."""
    pending = list(reqs)
    times = []
    steps = 0
    while pending or srv.busy:
        if pending and (steps < 2 or steps % 3 == 0):
            srv.submit(pending.pop(0))
        t0 = time.perf_counter()
        srv.step()
        times.append(time.perf_counter() - t0)
        steps += 1
        assert steps < 5000, "serving did not drain"
    return times


def serve_phase(cfg, params):
    eng = deepspeed_tpu.init_inference((cfg, params))
    where = {str(d) for x in jax.tree_util.tree_leaves(eng.params)
             for d in x.devices()}
    say(phase="serve_engine", decode_impl=eng.decode_impl,
        dtype=jnp.dtype(eng.dtype).name, params_on=sorted(where))
    assert eng.decode_impl == "pallas", eng.decode_impl
    assert len(where) == 1, f"one engine, one device: {where}"
    decode = eng._decode_slots = Recorder(eng._decode_slots)

    srv = ServingEngine(eng, num_slots=4)
    # warm-up: one request through every program the run will use
    t0 = time.perf_counter()
    warm = ServeRequest(rid="warm", max_new_tokens=4, prompt=np.arange(
        1, 131, dtype=np.int32))
    srv.run([warm])
    compile_s = time.perf_counter() - t0
    assert warm.state == "done", warm.state

    reqs = _requests(cfg, seed=1)
    with CompileWatch(0, label="serving steady steps"):
        times = _drive(srv, reqs)
    for r in reqs:
        assert r.state == "done" and len(r.out) == r.max_new_tokens, \
            (r.rid, r.state, len(r.out))
    say(phase="serve", layers=cfg.n_layers, num_slots=srv.num_slots,
        block_size=srv.cache.block_size, prefill_chunk=srv.prefill_chunk,
        requests=len(reqs), prompt_lens=[len(r.prompt) for r in reqs],
        new_tokens=32, all_done=True, steps=len(times),
        prefill_chunks=srv.stats["prefill_chunks"],
        decode_steps=srv.stats["decode_steps"],
        peak_occupancy=srv.stats["peak_occupancy"],
        warmup_s=round(compile_s, 1),
        steady_step_s=round(sorted(times)[len(times) // 2], 4))
    assert srv.stats["peak_occupancy"] > 1, "decode never batched"

    calls = mosaic_calls(decode.text)
    say(phase="serve_hlo", mosaic_calls=calls)
    assert any("paged_decode" in name for name, _ in calls), \
        "no paged_decode Mosaic call in the compiled decode-slots program"

    # the kernel against its reference at the served shapes
    L, N, bs, row = srv.cache.k.shape
    Hkv, Dh = cfg.kv_heads, cfg.head_dim
    assert row == Hkv * Dh, (srv.cache.k.shape, Hkv, Dh)
    nb = srv.cache.tables.shape[1]
    r = np.random.default_rng(2)
    k, v = (jnp.asarray(r.standard_normal((N, bs, row)), jnp.bfloat16)
            for _ in range(2))
    q = jnp.asarray(r.standard_normal((srv.num_slots, Hkv, 1, Dh)),
                    jnp.bfloat16)
    tables = jnp.asarray(1 + r.permutation(N - 1)[:srv.num_slots * nb]
                         .reshape(srv.num_slots, nb), jnp.int32)
    lengths = jnp.asarray(np.linspace(0, nb * bs - 1, srv.num_slots),
                          jnp.int32)
    scale = 1.0 / np.sqrt(Dh)
    out = jax.jit(lambda *a: paged.paged_decode_attention(*a, scale=scale))(
        q, k, v, tables, lengths)
    err = _err(out, paged.paged_decode_reference(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        tables, lengths, scale=scale))
    say(phase="paged_parity", shape=dict(slots=srv.num_slots, Hkv=Hkv, Dh=Dh,
                                         block=bs, blocks_per_slot=nb),
        err=round(err, 5), tol=FWD_TOL)
    assert err < FWD_TOL, err

    # the same requests through the gather path: online softmax reorders
    # bf16 sums and random weights sit on argmax ties, so the match rate
    # is printed, not asserted
    del srv
    gc.collect()
    ref_srv = ServingEngine(eng, num_slots=4, decode_impl="gather")
    ref_reqs = _requests(cfg, seed=1)
    _drive(ref_srv, ref_reqs)
    same = [sum(a == b for a, b in zip(x.out, y.out))
            for x, y in zip(reqs, ref_reqs)]
    prefix = [next((i for i, (a, b) in enumerate(zip(x.out, y.out))
                    if a != b), len(x.out)) for x, y in zip(reqs, ref_reqs)]
    say(phase="serve_vs_gather",
        token_match_rate=round(sum(same) / (32 * len(reqs)), 4),
        matching_prefix_lens=prefix)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth (never a width); default: all 48")
    args = ap.parse_args()

    from importlib.metadata import version
    cache_dir = setup_compile_cache()
    counts = {"hits": 0, "misses": 0, "compile_s": 0.0}

    def on_event(event, **kw):
        if event.endswith("/cache_hits"):
            counts["hits"] += 1
        elif event.endswith("/cache_misses"):
            counts["misses"] += 1

    def on_duration(event, duration, **kw):
        if "backend_compile" in event:
            counts["compile_s"] += duration

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    device = {"platform": DEV.platform, "kind": DEV.device_kind,
              "count": len(jax.devices())}
    say(phase="start", device=device, jax=jax.__version__,
        jaxlib=version("jaxlib"), libtpu=version("libtpu"),
        compile_cache_dir=cache_dir,
        cache_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")))

    over = {} if args.layers is None else {"n_layers": args.layers}
    cfg = gpt.preset(PRESET, max_seq_len=SEQ, dtype=jnp.bfloat16,
                     remat=True, remat_policy="full", flash_block_q=1024,
                     flash_block_kv=1024, loss_chunk=2048, **over)
    t0 = time.perf_counter()
    params = host_params(cfg)
    say(phase="params", preset=PRESET, layers=cfg.n_layers,
        d_model=cfg.d_model, heads=cfg.n_heads, head_dim=cfg.head_dim,
        n_params=gpt.num_params(cfg),
        host_init_s=round(time.perf_counter() - t0, 1))

    flash_parity()
    train_phase(cfg, params)
    gc.collect()
    serve_phase(cfg, params)

    say(phase="compile_cache", dir=cache_dir, hits=counts["hits"],
        misses=counts["misses"],
        backend_compile_s=round(counts["compile_s"], 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
