"""Per-request sampling subsystem for the continuous-batching slots.

Every per-request knob — temperature, top_k, top_p, seed,
repetition_penalty — lives as a **slot-indexed device array** (data,
not jit statics), so the two-program steady-state compile contract
(docs/SERVING.md) holds with arbitrarily mixed greedy/sampled batches:
the fused sampler below is traced INTO the prefill/decode slot
programs, and a request's knobs only change the values flowing through
the one compiled program, never its signature.

Three layers share this module:

- **Fused device sampler** (:func:`sample_tokens`): temperature scale →
  top-k mask → top-p nucleus mask → seeded categorical, vectorized over
  slots. temperature=0 lanes take the argmax lane and are BIT-IDENTICAL
  to the greedy serving output (the sampled machinery is where()-masked
  out of their result, not merely "close").
- **Per-slot key chain**: the categorical for the token at generation
  index ``i`` of a request seeded ``s`` uses
  ``fold_in(PRNGKey(s), i)`` — the fold happens on device inside the
  compiled program. Because the key is a pure function of
  ``(seed, tokens generated so far)`` there is no sequential RNG state
  to lose: eviction/requeue (which re-prefills prompt + partial output)
  and a router drain onto a survivor resume the chain exactly, and
  ``snapshot_entry``/``from_snapshot`` round-trip it by carrying the
  sampling params (docs/SAMPLING.md).
- **Host fp64 Leviathan primitives** (:func:`fp64_dist`,
  :func:`inverse_cdf`, :func:`accept_prob`, :func:`residual_dist`,
  :func:`spec_verify_tokens`): ONE implementation of the rejection-
  sampling accept/resample math (Leviathan et al. 2023 / Chen et al.
  2023) shared by the static speculative path
  (inference/speculative.py) and the serving spec-decode verify
  (serving.ServingEngine._spec_decode_step).
"""

import math
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# mask value for excluded tokens — matches engine._sample so the
# truncated distributions agree bitwise where both paths apply a mask
NEG_INF = -1e30

_U64 = (1 << 64) - 1


# ---------------------------------------------------------------------
# request-facing parameter bundle
# ---------------------------------------------------------------------
@dataclass(frozen=True)
class SamplingParams:
    """Resolved per-request sampling knobs (docs/SAMPLING.md).

    temperature=0 means greedy — and then every other knob is inert by
    contract (the greedy lane must stay bit-identical to the pre-
    sampling serving output, so no penalty/mask may perturb it)."""
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    repetition_penalty: float = 1.0

    def validate(self) -> "SamplingParams":
        if self.temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, "
                             f"got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0 (0 = off), "
                             f"got {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1] (1 = off), "
                             f"got {self.top_p}")
        if self.repetition_penalty <= 0.0:
            raise ValueError(f"repetition_penalty must be > 0, "
                             f"got {self.repetition_penalty}")
        return self

    @property
    def sampled(self) -> bool:
        return self.temperature > 0.0


def resolve_params(req, default_temperature: float = 0.0,
                   default_top_k: int = 0,
                   default_seed: int = 0) -> SamplingParams:
    """Per-request knobs win; engine-wide ctor defaults fill the gaps
    (a request field of None means "engine default")."""
    def pick(v, d):
        return d if v is None else v
    return SamplingParams(
        temperature=float(pick(getattr(req, "temperature", None),
                               default_temperature)),
        top_k=int(pick(getattr(req, "top_k", None), default_top_k)),
        top_p=float(pick(getattr(req, "top_p", None), 1.0)),
        seed=int(pick(getattr(req, "seed", None), default_seed)),
        repetition_penalty=float(pick(
            getattr(req, "repetition_penalty", None), 1.0)),
    ).validate()


def base_key(seed: int) -> np.ndarray:
    """[2] uint32 threefry key for a request seed — the root of the
    per-request key chain (host mirror; folds happen on device)."""
    return np.asarray(jax.random.PRNGKey(int(seed) & _U64), np.uint32)


def candidate_seed(seed: int, index: int) -> int:
    """Derived seed for candidate ``index`` of an n>1 request —
    SeedSequence-mixed so adjacent user seeds don't collide with
    adjacent candidate indices."""
    if index == 0:
        return int(seed)
    return int(np.random.SeedSequence([int(seed) & _U64, int(index)])
               .generate_state(1)[0])


# ---------------------------------------------------------------------
# fused slot-vectorized sampler (traced into the slot programs)
# ---------------------------------------------------------------------
@jax.named_scope("sample")
def sample_tokens(logits, keys, positions, temps, top_ks, top_ps,
                  rep_pens, seen):
    """Sample one token per slot from last-position ``logits`` [B, V].

    All knob arguments are slot-indexed arrays (DATA to jit, never
    statics): keys [B, 2] uint32 per-request base keys; positions [B]
    int32 tokens-generated-so-far (the key-chain counter); temps/
    top_ps/rep_pens [B] float32; top_ks [B] int32; seen [B, V] bool
    (tokens the repetition penalty applies to). Returns
    ``(tokens [B] int32, logprobs [B] float32)`` where the logprob is
    the chosen token's log-probability under the final (masked,
    renormalized) sampling distribution — or under plain
    softmax(logits) for greedy lanes.

    temperature<=0 lanes return ``argmax(logits.astype(f32))`` exactly
    (the greedy bit-identity contract); the sampled machinery below is
    masked out of their lane with where(), so its arithmetic can never
    perturb a greedy result. The whole sampled pipeline sits behind a
    ``lax.cond`` on "any lane sampled" — still ONE compiled program
    (both branches live in the same executable), but an all-greedy
    batch skips the mask/argsort/threefry work at RUNTIME, so greedy
    serving keeps its pre-sampling dispatch latency.
    """
    logits = logits.astype(jnp.float32)
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lane = temps > 0.0
    glps = jax.nn.log_softmax(logits, axis=-1)
    greedy_lp = jnp.take_along_axis(glps, greedy[:, None], axis=-1)[:, 0]

    def _sampled(_):
        # repetition penalty (CTRL-style): push seen tokens toward
        # "less likely" on the sampled lanes only
        pen = rep_pens[:, None]
        z = jnp.where(seen,
                      jnp.where(logits > 0, logits / pen, logits * pen),
                      logits)
        z = z / jnp.where(lane, temps, 1.0)[:, None]

        # one descending argsort serves both truncations, and the keep
        # mask is scattered back through it — no fp comparisons across
        # differently-ordered softmax reductions
        order = jnp.argsort(-z, axis=-1)
        z_sorted = jnp.take_along_axis(z, order, axis=-1)
        rank = jnp.arange(V, dtype=jnp.int32)[None, :]
        k = top_ks[:, None]
        keep = (k <= 0) | (rank < k)
        probs_sorted = jax.nn.softmax(jnp.where(keep, z_sorted, NEG_INF),
                                      axis=-1)
        csum = jnp.cumsum(probs_sorted, axis=-1)
        # nucleus: keep ranks whose EXCLUSIVE prefix mass is still
        # under top_p (the most-probable token always survives)
        tp = jnp.where(top_ps >= 1.0, jnp.inf, top_ps)[:, None]
        keep = keep & ((csum - probs_sorted) < tp)
        keep = keep.at[:, 0].set(True)
        inv = jnp.argsort(order, axis=-1)
        keep = jnp.take_along_axis(keep, inv, axis=-1)
        z = jnp.where(keep, z, NEG_INF)

        lane_keys = jax.vmap(jax.random.fold_in)(keys, positions)
        drawn = jax.vmap(jax.random.categorical)(lane_keys, z)
        slps = jax.nn.log_softmax(z, axis=-1)
        drawn_lp = jnp.take_along_axis(slps, drawn[:, None],
                                       axis=-1)[:, 0]
        return drawn.astype(jnp.int32), drawn_lp

    drawn, drawn_lp = jax.lax.cond(
        jnp.any(lane), _sampled, lambda _: (greedy, greedy_lp), None)
    tokens = jnp.where(lane, drawn, greedy)
    logprobs = jnp.where(lane, drawn_lp, greedy_lp)
    return tokens, logprobs


# ---------------------------------------------------------------------
# host-side slot state: the numpy mirrors the serving scheduler feeds
# to the fused sampler every step
# ---------------------------------------------------------------------
class SlotSamplerState:
    """Slot-indexed host mirrors of the sampling arrays.

    The scheduler owns one instance; rows are (re)written at admission
    and cleared at release. ``lanes()`` / ``lane()`` package them as the
    ``sample_state`` tuple the engine wrappers thread into the compiled
    slot programs: the small lanes as host values, which ride in the
    dispatch's one packed operand, and the ``seen`` mask as its copy on
    the device, uploaded again only after a row's content changed."""

    def __init__(self, num_slots: int, vocab_size: int):
        self.num_slots = num_slots
        self.vocab_size = vocab_size
        self.keys = np.zeros((num_slots, 2), np.uint32)
        self.temps = np.zeros(num_slots, np.float32)
        self.top_ks = np.zeros(num_slots, np.int32)
        self.top_ps = np.ones(num_slots, np.float32)
        self.rep_pens = np.ones(num_slots, np.float32)
        self.seen = np.zeros((num_slots, vocab_size), bool)
        # which rows of ``seen`` hold a mark, and the mask as the device
        # holds it (None: a row changed since). A greedy or unpenalized
        # request never marks its row, so admitting and releasing it
        # leaves the device's copy good
        self._marked = np.zeros(num_slots, bool)
        self._seen_device = None
        # called once per upload (ServingEngine: ``sampler_mask_uploads``)
        self.on_mask_upload = None

    def _set_row(self, slot: int, tokens=()) -> None:
        """Row ``slot`` of the mask becomes exactly ``tokens``."""
        marks = len(tokens) > 0
        if not (marks or self._marked[slot]):
            return
        self.seen[slot] = False
        if marks:
            self.seen[slot, np.asarray(tokens, np.int64) % self.vocab_size] \
                = True
        self._marked[slot] = marks
        self._seen_device = None

    def admit(self, slot: int, params: SamplingParams,
              tokens: Optional[Sequence[int]] = None) -> None:
        self.keys[slot] = base_key(params.seed)
        self.temps[slot] = params.temperature
        self.top_ks[slot] = params.top_k
        self.top_ps[slot] = params.top_p
        self.rep_pens[slot] = params.repetition_penalty
        self._set_row(slot, tokens if tokens is not None
                      and params.repetition_penalty != 1.0 else ())

    def release(self, slot: int) -> None:
        self.keys[slot] = 0
        self.temps[slot] = 0.0
        self.top_ks[slot] = 0
        self.top_ps[slot] = 1.0
        self.rep_pens[slot] = 1.0
        self._set_row(slot)

    def observe(self, slot: int, token: int) -> None:
        token = int(token) % self.vocab_size
        if self.rep_pens[slot] != 1.0 and not self.seen[slot, token]:
            self.seen[slot, token] = True
            self._marked[slot] = True
            self._seen_device = None

    def _mask(self):
        if self._seen_device is None:
            self._seen_device = jax.device_put(self.seen)
            if self.on_mask_upload is not None:
                self.on_mask_upload()
        return self._seen_device

    def lanes(self, gen_counts) -> Tuple:
        """The slot-batched ``sample_state`` tuple: gen_counts [B] is
        each slot's tokens-generated-so-far (the key-chain counter)."""
        return (self.keys, np.asarray(gen_counts, np.int32), self.temps,
                self.top_ks, self.top_ps, self.rep_pens, self._mask())

    def lane(self, slot: int, gen_count: int) -> Tuple:
        """Single-slot ``sample_state`` (the prefill-emit path): the
        slot's small lanes, then the slot, by which the program reads its
        row of the resident mask."""
        return (self.keys[slot], gen_count, self.temps[slot],
                self.top_ks[slot], self.top_ps[slot], self.rep_pens[slot],
                slot, self._mask())


def greedy_state(batch: int, vocab_size: int) -> Tuple:
    """All-greedy ``sample_state`` for legacy callers that only want
    logits back (every lane takes the argmax path)."""
    return (np.zeros((batch, 2), np.uint32), np.zeros(batch, np.int32),
            np.zeros(batch, np.float32), np.zeros(batch, np.int32),
            np.ones(batch, np.float32), np.ones(batch, np.float32),
            np.zeros((batch, vocab_size), bool))


# ---------------------------------------------------------------------
# shared fp64 Leviathan primitives (host side)
# ---------------------------------------------------------------------
def fp64_dist(logits, temperature: float, top_k: int = 0,
              top_p: float = 1.0) -> np.ndarray:
    """[..., V] logits -> fp64 probabilities at ``temperature``
    (optionally top_k/top_p-truncated). The temperature/top_k
    arithmetic is bit-for-bit the historical speculative.py ``dist``
    (the static-path parity pin in tests/test_speculative.py depends
    on that)."""
    z = np.asarray(logits, np.float64) / temperature
    if top_k > 0:
        k_eff = min(top_k, z.shape[-1])   # match generate()'s clamp
        kth = np.sort(z, axis=-1)[..., -k_eff, None]
        z = np.where(z < kth, -np.inf, z)
    z = z - z.max(-1, keepdims=True)
    e = np.exp(z)
    p = e / e.sum(-1, keepdims=True)
    if top_p < 1.0:
        p = nucleus_truncate(p, top_p)
    return p


def nucleus_truncate(p: np.ndarray, top_p: float) -> np.ndarray:
    """Zero everything outside the smallest probability-sorted prefix
    with cumulative mass >= top_p, then renormalize (rank-based cut,
    like the fused sampler: the most-probable token always survives)."""
    order = np.argsort(-p, axis=-1, kind="stable")
    p_sorted = np.take_along_axis(p, order, axis=-1)
    csum = np.cumsum(p_sorted, axis=-1)
    keep_sorted = (csum - p_sorted) < top_p
    keep_sorted[..., 0] = True
    keep = np.take_along_axis(keep_sorted, np.argsort(order, axis=-1),
                              axis=-1)
    out = np.where(keep, p, 0.0)
    return out / out.sum(-1, keepdims=True)


def inverse_cdf(p, u):
    """Inverse-CDF draw from probabilities ``p`` [..., V] with uniform
    ``u`` (scalar or [...]): index of the first cumsum bin above ``u``,
    clamped (fp rounding can leave cumsum[-1] < 1 and u above it)."""
    c = np.cumsum(np.asarray(p, np.float64), axis=-1)
    u = np.asarray(u, np.float64)
    while u.ndim < c.ndim:
        u = u[..., None]
    return np.minimum((u > c).sum(-1), c.shape[-1] - 1)


def accept_prob(px, qx):
    """Leviathan acceptance probability min(1, p(x)/q(x)) for the draft
    token x (elementwise over rows)."""
    return np.minimum(1.0, px / np.maximum(qx, 1e-300))


def residual_dist(p, q) -> np.ndarray:
    """Post-rejection resample distribution norm(max(0, p - q)) for one
    row, falling back to ``p`` when the residual has no mass (p == q)."""
    res = np.maximum(0.0, np.asarray(p, np.float64)
                     - np.asarray(q, np.float64))
    tot = res.sum()
    return res / tot if tot > 0 else np.asarray(p, np.float64)


def point_mass_residual(p: np.ndarray, x: int) -> np.ndarray:
    """residual_dist against a point mass at ``x`` — the deterministic-
    drafter case (serving's n-gram/greedy drafters propose one token
    with q(x) = 1): max(0, p - delta_x) is just p with x zeroed."""
    res = np.asarray(p, np.float64).copy()
    res[x] = 0.0
    tot = res.sum()
    return res / tot if tot > 0 else np.asarray(p, np.float64)


def position_uniforms(seed: int, pos: int, n: int = 2) -> np.ndarray:
    """Counter-based uniforms for deciding the token at generation
    index ``pos`` of a request seeded ``seed`` (Philox keyed by
    (seed, pos)). No sequential state: a verify chunk always starts at
    a committed token boundary, so evict/requeue and router drain
    replay the identical draws for every position they re-decide."""
    bits = np.random.Philox(key=[np.uint64(int(seed) & _U64),
                                 np.uint64(int(pos) & _U64)])
    return np.random.Generator(bits).random(n)


def spec_verify_tokens(p_rows, proposal, seed: int, pos0: int):
    """Leviathan verify of one slot's draft chunk against the target's
    verify distributions (the serving `_spec_decode_step` sampled lane).

    p_rows: [k+1, V] fp64 target distributions — row j is the
    distribution for the token at generation index ``pos0 + j``.
    proposal: [k] draft tokens from a DETERMINISTIC drafter (q is a
    point mass at the proposed token, so the acceptance probability
    min(1, p(x)/q(x)) reduces to p(x)). Returns
    ``(tokens, logprobs, n_accepted)``: the accepted prefix plus ONE
    correction token (residual-resampled at the first rejection) or
    bonus token (drawn from p at the position past the chunk).
    Logprobs are log p(token) under the target distribution at each
    position. Distribution-lossless: the emitted marginal equals
    sampling the target alone (docs/SAMPLING.md)."""
    toks, lps = [], []
    k = len(proposal)
    for j in range(k):
        x = int(proposal[j])
        u = position_uniforms(seed, pos0 + j, 2)
        px = float(p_rows[j][x])
        if u[0] < px:             # accept_prob(px, q=1) == px
            toks.append(x)
            lps.append(math.log(max(px, 1e-300)))
            continue
        res = point_mass_residual(p_rows[j], x)
        t = int(inverse_cdf(res, u[1]))
        toks.append(t)
        lps.append(math.log(max(float(p_rows[j][t]), 1e-300)))
        return toks, lps, j
    u = position_uniforms(seed, pos0 + k, 2)
    t = int(inverse_cdf(p_rows[k], u[0]))
    toks.append(t)
    lps.append(math.log(max(float(p_rows[k][t]), 1e-300)))
    return toks, lps, k
