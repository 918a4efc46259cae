"""Small seeded K-EXAONE-style models for the CPU tests: two periods of
LLLG with a leading dense layer, 8 experts, window 8; the plain reference
of the benchmark (benchmark/reference/exaone_moe.py) beside the program."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import exaone_moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reference():
    spec = importlib.util.spec_from_file_location(
        "exaone_moe_reference",
        os.path.join(ROOT, "benchmark", "reference", "exaone_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_config(held=(2, 4), window=8, max_seq_len=96, **over):
    kw = dict(
        vocab_size=96, n_layers=8, n_heads=4, n_kv_heads=2, d_model=32,
        head_size=16, d_ff=48, max_seq_len=max_seq_len, dtype=jnp.float32,
        attn_window=window, layer_kinds=("sliding",) * 3 + ("full",)
        + ("sliding",) * 3 + ("full",), n_dense_layers=1, num_experts=8,
        moe_k=3, moe_d_ff=24, n_shared_experts=1, routed_scaling=2.5,
        experts_held=held, use_flash_attention=False)
    kw.update(over)
    return exaone_moe.ExaoneMoEConfig(**kw)


def tiny_params(cfg, seed=0):
    # a larger std than the family's 0.02: at width 32 it keeps every
    # term of the equations visible in the logits
    return exaone_moe.init_params(jax.random.PRNGKey(seed), cfg, std=0.2,
                                  bias_std=0.05)


def hp_of(cfg):
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.kv_heads,
            "head_dim": cfg.head_dim, "window": cfg.attn_window,
            "kinds": tuple(cfg.layer_kinds), "n_dense": cfg.n_dense_layers,
            "num_experts": cfg.num_experts, "top_k": cfg.moe_k,
            "held": tuple(cfg.held), "routed_scale": cfg.routed_scaling,
            "eps": cfg.norm_eps, "rope_theta": cfg.rope_theta}


def serve_logits(cfg, params, prompts, new_tokens, num_slots=2,
                 prefill_chunk=16, block_size=4, **kw):
    """Run ``prompts`` through ServingEngine (greedy); returns (srv,
    {rid: (tokens [P + n], logits [n, V] of every emitted token)})."""
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
    eng = deepspeed_tpu.init_inference(
        (cfg, jax.tree_util.tree_map(np.asarray, params)), dtype=cfg.dtype)
    srv = ServingEngine(eng, num_slots=num_slots, block_size=block_size,
                        prefill_chunk=prefill_chunk, **kw)
    got = {}
    orig_p, orig_d = eng.prefill_into_slot, eng.decode_slots

    def prefill(*a, **k):
        out = orig_p(*a, **k)
        prefill.last = np.asarray(out[0], np.float32).reshape(-1)
        return out

    def decode(*a, **k):
        out = orig_d(*a, **k)
        decode.last = np.asarray(out[0], np.float32)
        return out

    eng.prefill_into_slot, eng.decode_slots = prefill, decode
    reqs = [ServeRequest(rid=i, prompt=np.asarray(p, np.int32),
                         max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)]
    for r in reqs:
        srv.submit(r)
    seen = {r.rid: 0 for r in reqs}
    logits = {r.rid: [] for r in reqs}
    guard = 0
    while srv.busy:
        slot_of = {s.rid: i for i, s in enumerate(srv.slots) if s is not None}
        before = {r.rid: len(r.out) for r in reqs}
        prefill.last = decode.last = None
        srv.step()
        slot_of.update({s.rid: i for i, s in enumerate(srv.slots)
                        if s is not None})
        for r in reqs:
            new = len(r.out) - before[r.rid]
            # the first token comes from the final prefill chunk, every
            # other from the decode dispatch, at the slot the request sits in
            if new and before[r.rid] == 0:
                logits[r.rid].append(prefill.last)
                new -= 1
            if new:
                assert new == 1, new
                logits[r.rid].append(decode.last[slot_of[r.rid]].reshape(-1))
        guard += 1
        assert guard < 2000
    for r in reqs:
        assert r.state == "done", r.state
        got[r.rid] = (np.concatenate([r.prompt, np.asarray(r.out, np.int32)]),
                      np.stack(logits[r.rid]))
    return srv, got


def serve_chunks(cfg, params, prompts, new_tokens, whole=False, **kw):
    """:func:`serve_logits` one request at a time, and beside it every
    prefill chunk's ``(start, n_valid, logits of its last token)`` in the
    order served. ``whole``: with ONE length to read compiled in
    (engine.PREFILL_READ_LENGTHS 1): every chunk attends its slot's whole
    row and the whole ring, the form before the tiles."""
    import pytest
    from deepspeed_tpu.inference import engine
    chunks = []
    orig = engine.InferenceEngine.prefill_into_slot

    def prefill(self, k_pool, v_pool, table_row, tokens, start, n_valid,
                **k):
        out = orig(self, k_pool, v_pool, table_row, tokens, start, n_valid,
                   **k)
        chunks.append((int(start), int(n_valid),
                       np.asarray(out[0], np.float32).reshape(-1)))
        return out

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine.InferenceEngine, "prefill_into_slot", prefill)
        if whole:
            mp.setattr(engine, "PREFILL_READ_LENGTHS", 1)
        srv, got = serve_logits(cfg, params, prompts, new_tokens,
                                num_slots=1, **kw)
    return srv, got, chunks


def gathered_blocks(jaxpr, pool_block):
    """Leading sizes of the gathers of whole pool blocks (results ``[n,
    *pool_block]``) in ``jaxpr``: (those outside every ``lax.switch``,
    [per switch, in program order: per branch, the sizes inside it])."""
    def subs(eqn):
        for v in eqn.params.values():
            for j in v if isinstance(v, (tuple, list)) else (v,):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    yield j

    def walk(j, outside, switches):
        for eqn in j.eqns:
            if eqn.primitive.name == "gather":
                shape = eqn.outvars[0].aval.shape
                if shape[1:] == tuple(pool_block):
                    outside.append(shape[0])
            elif eqn.primitive.name == "cond" \
                    and len(eqn.params["branches"]) > 2:
                branches = []
                for b in eqn.params["branches"]:
                    branches.append([])
                    walk(b.jaxpr, branches[-1], switches)
                switches.append(branches)
            else:
                for sub in subs(eqn):
                    walk(sub, outside, switches)
        return outside, switches

    return walk(getattr(jaxpr, "jaxpr", jaxpr), [], [])


# what each prompt's chunks meet, at chunks of 40 (off a block's edge), blocks
# of 16 and rows of 1,024 positions: 8 tiles of 128
EDGES = {"one short of a tile": 127, "on a tile": 128, "one past a tile": 129,
         "every length of the row, a padded last chunk": 1000}
EDGE = dict(prefill_chunk=40, block_size=16)


def serve_edges(cfg, params):
    """The EDGES prompts served as the program serves them and in the
    whole-row, whole-ring form: (prompts, srv, (got, chunks), (got, chunks)
    of the whole form)."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 96, n) for n in EDGES.values()]
    srv, *tiles = serve_chunks(cfg, params, prompts, 3, **EDGE)
    _, *whole = serve_chunks(cfg, params, prompts, 3, whole=True, **EDGE)
    return prompts, srv, tiles, whole


def assert_tiles_as_whole(edges, rid):
    """Every chunk of prompt ``rid`` gives the logits of the form that
    attends the slot's whole row and the whole ring whatever they hold, to
    float32 rounding, and so do the tokens behind it; what the scheduler
    counts as read is the full layers' whole tiles of 128 up to the chunk's
    end, not the row's 1,024."""
    prompts, srv, (got, chunks), (got_whole, whole) = edges
    C = EDGE["prefill_chunk"]
    first = sum(-(-len(p) // C) for p in prompts[:rid])
    mine = chunks[first:first + -(-len(prompts[rid]) // C)]
    assert [(s, n) for s, n, _ in mine] == [
        (s, min(C, len(prompts[rid]) - s))
        for s in range(0, len(prompts[rid]), C)]
    for (s, n, lg), (sw, nw, lw) in zip(mine, whole[first:]):
        assert (s, n) == (sw, nw)
        np.testing.assert_allclose(lg, lw, atol=2e-5, err_msg=str((s, n)))
    np.testing.assert_array_equal(got[rid][0], got_whole[rid][0])
    np.testing.assert_allclose(got[rid][1], got_whole[rid][1], atol=2e-5)
    assert [srv.engine.prefill_attended(s, n, 16, 64) for s, n, _ in mine] \
        == [-(-(s + n) // 128) * 128 for s, n, _ in mine]
