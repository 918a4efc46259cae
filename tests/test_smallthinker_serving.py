"""SmallThinker (models/smallthinker.py: data of the config on the hybrid
dialect) on the paged serving path, held to the benchmark's plain reference
at small sizes: the router that reads the layer's input before attention,
ReLU-gated experts, a window ring that wraps beside one that never fills,
the controls, the counters and gauges it added."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import smallthinker_util as U
import exaone_moe_util as X
import program_text as PT
from exaone_moe_util import serve_logits
from deepspeed_tpu.inference import dialect, hybrid
from deepspeed_tpu.models import exaone_moe, smallthinker
from deepspeed_tpu.moe import expert_share

SOUND = 2e-4        # float32 program against the float32 reference
WRONG = 2e-2        # every control moves the logits by more than this
VARIANTS = ["router_after_attention", "router_normed", "silu",
            "softmax_all_unnormalised", "rotary_on_full", "no_rotary",
            "window_off_by_one", "qk_norm"]


@pytest.fixture(scope="module")
def served():
    cfg = U.tiny_config()
    params = U.tiny_params(cfg)
    rng = np.random.default_rng(0)
    # window 8, block 4: a ring of 3 blocks = 12 places. 41 + 6 tokens go
    # round it three times, across chunk boundaries (16) and off a block's
    # edge; 4 + 6 tokens never fill the ring. Served together
    prompts = [rng.integers(1, 96, 41), rng.integers(1, 96, 4)]
    srv, got = serve_logits(cfg, params, prompts, 6, telemetry=True)
    return cfg, params, prompts, srv, got


def _worst(cfg, params, prompts, got, **kw):
    worst = {}
    for rid, (toks, lg) in got.items():
        want, _ = U.reference().logits(params, toks[:-1], U.hp_of(cfg), **kw)
        want = np.asarray(want)[len(prompts[rid]) - 1:]
        worst[rid] = float(np.abs(lg - want).max())
    return worst


def test_the_dialect_is_hybrids_and_the_ring_is_the_windows(served):
    cfg, _, _, srv, _ = served
    assert dialect.of(cfg) is hybrid.DIALECT
    assert cfg.layer_kinds[:4] == ("full", "sliding", "sliding", "sliding")
    assert exaone_moe.window_blocks(cfg, 4) == 3
    assert srv.cache.k.win.shape[:2] == (6, 1 + 2 * 3)
    assert srv.cache.k.full.shape[0] == 2
    assert srv.cache.ring_rows_allocated == 2 * 3 * 4 * 6


def test_prefill_then_decode_matches_the_reference(served):
    cfg, params, prompts, _, got = served
    # the short request ends at 4 + 6 = 10 tokens, inside its ring's 12
    # places: it never wrapped; the long one went round three times
    worst = _worst(cfg, params, prompts, got)
    assert worst[0] < SOUND and worst[1] < SOUND, worst


def test_score_blocks_bound_the_chunks_temporaries(served, monkeypatch):
    """At the cell's sizes a chunk's float32 scores pass SCORE_BYTES: a
    window layer then attends a KV head at a time and a full layer's longer
    branches a block of queries at a time. Forced here by a tiny bound: the
    same logits."""
    cfg, params, prompts, _, got = served
    assert hybrid._score_blocks(512, 512 * 7 * 16384) == 4
    assert hybrid._score_blocks(512, 512 * 7 * 4096) == 1
    assert hybrid._score_blocks(512, 512 * 28 * 1152) == 1      # one tile
    assert hybrid._score_blocks(512, 512 * 28 * 4992) == 8
    assert hybrid._score_blocks(256, 256 * 8 * 4096) == 1       # K-EXAONE
    assert hybrid._score_blocks(256, 256 * 64 * 400) == 1
    monkeypatch.setattr(hybrid, "SCORE_BYTES", 1 << 12)
    assert hybrid._score_blocks(16, 16 * 2 * 96) == 4
    _, cut = serve_logits(cfg, params, prompts, 6)
    for rid in got:
        np.testing.assert_array_equal(got[rid][0], cut[rid][0])
        np.testing.assert_allclose(got[rid][1], cut[rid][1], atol=2e-5)


# window 300 at blocks of 16: a ring of 20 blocks read in tiles of 8, three
# lengths, the longest past the ring's end; the 1,000-token prompt passes the
# window and goes round the ring
@pytest.fixture(scope="module")
def edges():
    cfg = U.tiny_config(window=300, max_seq_len=1024)
    params = U.tiny_params(cfg)
    return cfg, params, X.serve_edges(cfg, params)


@pytest.mark.parametrize("rid", range(len(X.EDGES)), ids=list(X.EDGES))
def test_a_chunk_attends_the_tiles_it_sees_as_the_whole_row_and_ring(
        edges, rid):
    cfg, params, served = edges
    X.assert_tiles_as_whole(served, rid)
    prompts, _, (got, _), _ = served
    assert _worst(cfg, params, prompts, {rid: got[rid]})[rid] < SOUND


def test_no_chunk_gathers_a_whole_row_or_ring_but_in_the_longest_branch():
    """The prefill program's reads of pool blocks all lie in the branches
    of a ``lax.switch``, a tile more in each: 8 lengths of the 64-entry row
    in a full layer, 3 of the 20-block ring (the longest run past its end)
    in a window layer, K and V alike."""
    cfg = U.tiny_config(window=300, max_seq_len=1024)
    params = jax.eval_shape(lambda: U.tiny_params(cfg))
    rows = cfg.kv_heads * cfg.head_dim
    text = PT.serving_programs(cfg, params, C=40, bs=16, NB=64)
    outside, switches = X.gathered_blocks(text["prefill_slot"], (16, rows))
    # the 4 windows of a block in which write_chunk lays the chunk's rows
    assert outside == [4, 4]
    assert sorted(switches, key=len) == [
        [[8 * n] * 2 for n in range(1, 4)],
        [[8 * n] * 2 for n in range(1, 9)]]


@pytest.mark.parametrize("variant", VARIANTS)
def test_each_variant_of_the_reference_differs(served, variant):
    cfg, params, prompts, _, got = served
    err = _worst(cfg, params, prompts, {0: got[0]}, variant=(variant,))[0]
    assert err > WRONG, (variant, err)


def test_precision_control_differs(served):
    cfg, params, prompts, _, got = served
    assert _worst(cfg, params, prompts, {0: got[0]}, fp8=True)[0] > WRONG


def test_router_runs_before_attention_in_both_programs():
    import functools
    import program_text as PT
    cfg = U.tiny_config()
    params = jax.eval_shape(functools.partial(U.tiny_params, cfg))
    for name, text in PT.serving_programs_text(cfg, params).items():
        # the first two products of the layer body: the router's [T, 8]
        # logits, THEN the qkv projection's 128 lanes
        body = text[text.index("scan["):]
        dots = [ln.split("=")[0] for ln in body.splitlines()
                if "dot_general[" in ln][:2]
        assert dots[0].strip().endswith(",8]"), (name, dots)
        assert dots[1].strip().endswith(",128]"), (name, dots)


def test_softmax_renorm_is_softmax_over_the_chosen():
    cfg = U.tiny_config()
    p = jax.tree_util.tree_map(lambda a: a[1], U.tiny_params(cfg)["block"])
    x = jax.random.normal(jax.random.PRNGKey(5), (24, cfg.d_model)) * 3
    sel, w = expert_share.route_by_config(x, p["moe"]["router"], cfg)
    z = np.asarray(x, np.float64) @ np.asarray(
        p["moe"]["router"]["kernel"], np.float64)
    np.testing.assert_array_equal(np.sort(np.asarray(sel), -1),
                                  np.sort(np.argsort(-z, -1)[:, :3], -1))
    zs = np.take_along_axis(z, np.asarray(sel), -1)
    want = np.exp(zs - zs.max(-1, keepdims=True))
    want = want / want.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w), want, atol=1e-6)


def test_published_parameter_count():
    cfg = smallthinker.SmallThinkerConfig(
        vocab_size=151936, n_layers=52, n_heads=28, n_kv_heads=4,
        d_model=2560, head_size=128, max_seq_len=16384,
        layer_kinds=smallthinker.layer_kinds([0, 1, 1, 1] * 13,
                                             [0, 1, 1, 1] * 13, 52))
    assert smallthinker.num_params(cfg) == 21_506_562_560
    assert (cfg.attn_window, cfg.num_experts, cfg.moe_k, cfg.moe_d_ff) \
        == (4096, 64, 6, 768)
    first_stage = smallthinker.SmallThinkerConfig(
        vocab_size=151936, n_layers=12, n_heads=28, n_kv_heads=4,
        d_model=2560, head_size=128, max_seq_len=16384,
        layer_kinds=smallthinker.layer_kinds([0, 1, 1, 1] * 13,
                                             [0, 1, 1, 1] * 13, 12))
    assert smallthinker.num_params(first_stage) == 5_561_448_960
    shapes = jax.eval_shape(lambda: smallthinker.init_params(
        jax.random.PRNGKey(0), U.tiny_config()))
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) \
        == smallthinker.num_params(U.tiny_config())
    with pytest.raises(AssertionError):
        smallthinker.layer_kinds([0, 1, 1, 1], [1, 1, 1, 1], 4)


def test_one_chip_shares_a_layer_so_the_held_share_is_the_uncut_layer():
    """The share test in its degenerate form: one chip holds all 8 experts,
    so what it computes is the reference's whole expert layer."""
    ref = U.reference()
    cfg = U.tiny_config()
    assert cfg.held == (0, cfg.num_experts)
    p = jax.tree_util.tree_map(lambda a: a[2], U.tiny_params(cfg)["block"])
    x = jax.random.normal(jax.random.PRNGKey(3), (24, cfg.d_model)) * 2
    h = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.d_model))
    sel, w = expert_share.route_by_config(x, p["moe"]["router"], cfg)
    got, stats = expert_share.held_experts_ffn(
        h, p["moe"]["experts"], sel, w, cfg.held, "ragged_dot", act="relu")
    free = -jnp.ones((24, cfg.moe_k), jnp.int32)
    with jax.default_matmul_precision("highest"):
        z = x @ p["moe"]["router"]["kernel"]
        want, own = ref._experts(jnp.zeros_like(h), h, z, p, U.hp_of(cfg),
                                 frozenset(), False, free)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    np.testing.assert_array_equal(np.sort(np.asarray(sel), -1),
                                  np.sort(np.asarray(own), -1))
    names = expert_share.stat_fields(cfg)
    assert names[5:7] == ("act_zero", "act_total")
    stats = dict(zip(names, (int(v) for v in stats)))
    assert stats["pairs_held"] == stats["pairs_total"] == 24 * 3
    assert stats["act_total"] == 24 * 3 * cfg.moe_d_ff // 16
    assert 0 < stats["act_zero"] < stats["act_total"]


def test_counters_gauges_and_the_span_field(served):
    cfg, _, prompts, srv, _ = served
    got = srv.read_expert_counters()
    for phase, tokens in (("prefill", 45), ("decode", 10)):
        c = got[phase]
        assert c["pairs_held"] == c["pairs_total"] \
            == tokens * cfg.moe_k * cfg.n_layers
        assert c["act_total"] > 0 and 0 < c["act_zero"] < c["act_total"]
    text = str(srv.metrics.snapshot())
    assert "kv_window_ring_rows_used" in text
    assert "kv_window_ring_rows_allocated" in text
    assert "moe_decode_act_zero" in text
    wrapped = {(s[5]["start"], s[5]["ring_wrapped"])
               for s in srv.telemetry.tracer.spans("serve.prefill")
               if s[5]["n"] > 4}
    assert wrapped == {(0, 1), (16, 1), (32, 1)}
    srv.cache.lengths[:] = [5, 30]
    assert srv.cache.ring_rows_used == (5 + 8) * 6
    assert srv.cache.ring_wrapped(9) and not srv.cache.ring_wrapped(8)


def test_k_exaone_keeps_its_data():
    import exaone_moe_util as X
    cfg = X.tiny_config()
    assert expert_share.expert_act(cfg) == "silu"
    assert expert_share.stat_fields(cfg) == expert_share.STAT_FIELDS
    p = jax.tree_util.tree_map(lambda a: a[0], X.tiny_params(cfg)["block"])
    assert hybrid.route_layer_input(jnp.zeros((1, 3, 32)), p, cfg) is None
