"""Continuous-batching serving scheduler over the paged KV-cache.

The static engine runs ONE fixed batch to completion: every row pays for
the slowest request, and a new arrival waits for the whole batch to
drain. This scheduler implements iteration-level (continuous) batching
as in Orca (Yu et al., OSDI '22): a fixed set of decode SLOTS, and on
every iteration

1. **expiry** — requests past their ``deadline`` retire with
   ``state="timeout"`` (partial tokens kept) instead of squatting a
   slot or queue position;
2. **admission** — queued requests claim free slots if the paged cache
   can cover their prompt while keeping the watermark reserve;
3. **prefill** — newly admitted requests prefill their prompt into
   their slot in fixed-width CHUNKS (one chunk per iteration per slot),
   so a long prompt never stalls the running decode batch for more than
   one chunk's latency;
4. **decode** — all decoding slots advance one token through the single
   compiled ``decode_slots`` program, each at its own position.

On cache exhaustion mid-decode the scheduler EVICTS the most recently
admitted request instead of OOMing: its blocks return to the pool and
the request requeues (front of the queue) with prompt+generated as its
new prompt — recompute-on-resume reproduces the exact pre-eviction
state, so greedy outputs are untouched (vLLM's recompute preemption).
``max_evictions`` caps how often one request may be preempted: a
request at the cap is PINNED (never chosen as a victim again), so an
eviction storm cannot livelock requeued work — the oldest pinned
request always runs to completion.

Graceful degradation (the chaos contract, tests/test_chaos.py):

- **bounded queue + load shedding** — with ``max_queue`` set, a submit
  into a full queue retires the NEWEST request with ``state="shed"``
  (reject-newest keeps already-accepted work's latency predictable);
  ``stats["backpressure"]`` exposes queue fullness in [0, 1] for
  upstream admission control;
- **retry with backoff** — transient device errors
  (:class:`~deepspeed_tpu.utils.faults.TransientDeviceError`) around the
  two slot programs retry up to ``max_retries`` times with exponential
  backoff and deterministic (seeded) jitter; faults fire BEFORE
  dispatch, so the donated pools are still valid on every retry;
- **step watchdog** — with ``step_time_budget_s`` set, ``watchdog_grace``
  consecutive over-budget decode dispatches raise a structured
  :class:`DegradedError` carrying every finished result and a snapshot
  of in-flight work (nothing is thrown away), instead of hanging;
- **fault injection** — the engine consults the ambient
  :mod:`deepspeed_tpu.utils.faults` injector (or one passed as
  ``faults=``) at the ``serving.decode`` / ``serving.prefill`` sites;
  the paged cache exposes ``cache.allocate`` / ``cache.ensure``.

Shared-prefix caching (``prefix_cache=True`` / ``DS_PREFIX_CACHE=on``,
docs/PREFIX_CACHE.md): admission asks the cache to match the request's
longest cached prefix — shared blocks map into the slot read-only and
PREFILL STARTS AT THE MATCHED BOUNDARY (``_progress`` begins at the
matched token count, so a fully-cached system prompt costs zero prefill
chunks beyond its uncached tail). When the prompt finishes prefilling,
its full blocks are published to the index for the next request.
``stats["prefix_hits"]`` / ``stats["prefix_tokens_saved"]`` count the
win; ``_finish``/``_preempt`` release REFERENCES, not blocks — a block
another slot still maps, or one the index keeps as reusable cache,
stays resident. Warm-vs-cold token parity is exact: the prefill program
is chunk-boundary invariant (fixed-width chunks, gather over the full
table, causal mask), so starting at a nonzero offset over shared blocks
reproduces the cold logits bit-for-bit (tests/test_prefix_cache.py).

Speculative decoding (``spec_decode=True`` / ``DS_SPEC_DECODE=on``,
docs/SPECULATIVE.md): each decode iteration a DRAFTER (prompt-lookup
n-grams by default — no second model) proposes ``spec_k`` tokens per
live slot; one compiled verify program (``engine.verify_slots``) scores
all ``spec_k + 1`` chunk positions per slot against the paged cache,
and each slot independently accepts its longest draft prefix agreeing
with the target's own greedy argmax, emitting ``accepted + 1`` tokens
(the ``+1`` is the target's correction — the classic draft-verify
free token). The first reject rolls the slot's cache back
(``cache.rollback``): lengths shrink past the rejected suffix and tail
blocks only that suffix touched return to the pool; stale K/V inside
kept blocks is overwritten by the next chunk before any query attends
it. A temperature=0 slot accepts its longest prefix agreeing with the
target's own greedy argmax, which makes its spec-on output BIT-
IDENTICAL to spec-off greedy serving (tests/test_spec_serving.py pins
this across eviction/requeue and prefix-cache hits); a sampled slot
runs per-position rejection-sampling verify (Leviathan/Chen), which is
DISTRIBUTION-lossless against plain sampled decode (docs/SAMPLING.md).
Speculation only changes how many steps the tokens take. An injected
draft/verify fault degrades that step to the plain one-token path
(``stats["spec_fallbacks"]``) — chaos turns speculation off, never
output wrong.

The steady state is two compiled programs (prefill chunk, slot decode —
with speculation on, the ``spec_k + 1``-position verify program REPLACES
slot decode) regardless of arrival pattern; all scheduling state is
host numpy. None
of the robustness paths (deadlines, shedding, backoff, expiry) touch
device shapes, so the compile-count contract is unchanged — pinned by
``test_serving_compile_count_contract`` and its chaos twin. The prefix
cache adds ONE more program (the copy-on-write block copy), compiled
eagerly at construction via ``cache.warm_cow()`` so steady state stays
recompile-free with the cache on.

A step does not walk its slots in Python. What the scheduler needs of
every slot's request each step lives in per-slot arrays beside
``cache.lengths`` (the last emitted token, the generated count,
``max_new_tokens``, ``eos_id``, the deadline, which phase holds the slot),
written where a slot changes hands (``_seat`` / ``_vacate``) and per
token by the emit. Expiry is one comparison with the deadlines;
capacity one comparison with the block tables, and only a slot at a
block border (once in ``block_size`` steps) or at its budget reaches
``ensure_capacity`` and its eviction ladder; the decode program's
``tokens`` / ``active`` / ``gen_counts`` operands are the arrays; the
emit is one ``lengths[live] += 1``, one ``tolist()``, two appends a
request (``out`` and ``token_times`` stay lists, current when ``step``
returns) and the terminal test as one array expression. The per-slot
path (``_emit_sampled`` > ``_emit_token``) is taken by the slots whose
request needs per-token Python — stop sequences, ``logprobs``, a
repetition penalty (``_slow_emit``) — and by the horizon and speculative
steps; both kinds of slot sit in one step, and requests finish in
ascending slot order either way. Nothing is read from a flag: which
path a slot takes is a property of its request and of its table.

Telemetry (``telemetry=True`` / ``DS_TELEMETRY=on``,
docs/OBSERVABILITY.md): every lifecycle transition (enqueue, admit with
prefix-hit tags, prefill chunks, evict/requeue, finish/timeout/shed),
injected faults and the spans inside a step (``serve.step`` and below)
stream into a :class:`~deepspeed_tpu.telemetry.Telemetry` bundle —
ring-buffered host-side records plus a metrics registry with Prometheus and
Chrome-trace/Perfetto exporters. ``stats`` is now a READ-ONLY mapping
view over registry counters (same keys, same values as the old dict);
the scheduler deadline clock is a private field, so mutating a metric
can never move a deadline. Default off: the off path swaps in no-op
twins and is token-bit-identical to on (tests/test_telemetry.py). The
plane does not walk the slots either: a batched dispatch, a step's
block-seconds and a step's TPOT are one call each over the slots'
arrays (telemetry/costs.py ``charge_batched``, metrics.py
``observe_many``), a slot's cost accumulators fold into its request
where the slot is vacated, and every dispatch's ring record says what
the plane itself took of the host time before it (``self_us`` /
``self_parts``, ``_account_gap``).

Per-request sampling (docs/SAMPLING.md): every ``ServeRequest`` may
carry its own temperature/top_k/top_p/seed/repetition_penalty plus
``stop`` sequences, ``logprobs``, and ``n`` candidates. The knobs ride
as slot-indexed DEVICE ARRAYS into the fused sampler that is traced
inside the prefill/decode slot programs (inference/sampling.py) —
data, not jit statics — so arbitrarily mixed greedy/sampled batches
keep the two-program compile contract, and greedy slots in a mixed
batch stay bit-identical to an all-greedy run. The per-token key is
``fold_in(PRNGKey(seed), tokens_generated)``, a pure function of
request state, so eviction/requeue and router drain resume a sampled
stream bit-exactly (spec-decode sampled verify is the one documented
exception: distribution-lossless, deterministic per run history, not
bit-stable across a mid-stream resume).

Greedy parity contract (tested): for any arrival pattern, every
temperature=0 request's output is token-for-token identical to a solo
``InferenceEngine.generate`` run of its prompt.
"""

import json
import math
import time
from collections import deque
from collections.abc import Mapping
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference import sampling
from deepspeed_tpu.inference.adapters import (AdapterLoadError, AdapterPool,
                                              resolve_lora_serve)
from deepspeed_tpu.inference import dialect
from deepspeed_tpu.inference.host_tier import resolve_host_tier
from deepspeed_tpu.inference.paged_cache import (CacheExhausted,
                                                 PagedKVCache, chunk_blocks,
                                                 resolve_prefix_cache)
from deepspeed_tpu.inference.spec_decode import (make_draft,
                                                 resolve_spec_decode,
                                                 resolve_spec_k)
from deepspeed_tpu.ops.attention.paged import tiles_run
from deepspeed_tpu.ops.quantizer import resolve_kv_quant
from deepspeed_tpu.telemetry import (NOOP, MetricsRegistry, NoopTelemetry,
                                     RATE_BUCKETS, TEMP_BUCKETS, Telemetry,
                                     resolve_telemetry)
from deepspeed_tpu.telemetry.costs import (CostAccountant, NOOP_COSTS,
                                           ProgramCostRegistry)
from deepspeed_tpu.telemetry.costs import new_footprint as _new_footprint
from deepspeed_tpu.telemetry.flight import FlightRecorder, NOOP_FLIGHT
from deepspeed_tpu.utils import faults as faults_lib
from deepspeed_tpu.utils.env import (flag_names, resolve_decode_horizon,
                                     resolve_flag)
from deepspeed_tpu.utils.faults import TransientDeviceError
from deepspeed_tpu.utils.logging import logger

TERMINAL_STATES = ("done", "timeout", "shed", "error")

# in-program stop-sequence modeling caps for the fused multi-step decode
# (docs/MULTISTEP.md): a stop longer than HORIZON_STOP_WIDTH tokens, or
# past the first HORIZON_MAX_STOPS sequences, is left unmodeled — its
# lane free-runs inside the horizon and the authoritative host-side
# check truncates the stream at the true hit, so tokens stay exact;
# only the early-freeze optimization is lost for that request
HORIZON_STOP_WIDTH = 8
HORIZON_MAX_STOPS = 4

# wall-seconds ladder of the serving_step_*_s histograms: scheduler
# phases run 10us..1s on CPU/TPU hosts
_STEP_PHASES = ("admission", "prefill", "decode", "bookkeeping")
_PHASE_BUCKETS = (1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3,
                  5e-3, 1e-2, 2.5e-2, 5e-2, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0)

# the stats contract: same keys (and order) as the pre-telemetry dict,
# now backed by registry metrics ("c" counter / "g" gauge) and exposed
# through the read-only _StatsView
_STAT_FIELDS = (
    ("steps", "c", "scheduler iterations"),
    ("occupancy_sum", "c", "sum of per-step decode occupancy"),
    ("peak_occupancy", "g", "max decode occupancy seen"),
    ("evictions", "c", "preemptions (recompute-on-resume requeues)"),
    ("admitted", "c", "requests admitted to a slot"),
    ("completed", "c", "requests finished with state=done"),
    ("prefill_chunks", "c", "prefill chunk dispatches"),
    ("prefill_write_blocks_total", "c",
     "blocks of their slots' rows in which prefill chunks have a row "
     "(paged_cache.chunk_blocks: what a chunk's write moves, whole)"),
    ("decode_steps", "c", "batched decode dispatches"),
    ("timeouts", "c", "requests retired at their deadline"),
    ("shed", "c", "requests rejected by the bounded queue"),
    ("retries", "c", "transient-device-error retries"),
    ("evict_capped", "c", "evictions refused by the storm guard"),
    ("watchdog_trips", "c", "over-budget decode dispatches"),
    ("backpressure", "g", "queue fullness in [0, 1]"),
    ("prefill_attended_tokens_total", "c",
     "positions of their slots' rows that prefill chunks read from the "
     "pool (InferenceEngine.prefill_attended: the occupied part)"),
    ("prefill_row_tokens_total", "c",
     "positions of a whole row per prefill chunk; attended / row is the "
     "share of the row a chunk still reads"),
    ("mla_prefill_tiles_kernel_total", "c",
     "flash steps of prefill chunks' latent layers taken by the "
     "mla_prefill kernel (InferenceEngine.mla_prefill_tiles: latent "
     "layers x (occupied history blocks + the chunk's own tile))"),
    ("mla_prefill_tiles_plain_total", "c",
     "the same steps where the engine's decode_impl is not pallas: "
     "plain flash steps (latent._attend_tile)"),
    ("prefix_hits", "c", "admissions that matched a cached prefix"),
    ("prefix_tokens_saved", "c", "prompt tokens served from shared blocks"),
    ("spec_steps", "c", "speculative verify dispatches"),
    ("spec_slot_steps", "c", "per-slot verify participations"),
    ("spec_proposed", "c", "draft tokens offered for verification"),
    ("spec_accepted", "c", "draft tokens accepted by the target"),
    ("spec_emitted", "c", "tokens emitted by speculative steps"),
    ("spec_fallbacks", "c", "spec steps degraded to plain decode"),
    ("horizon_fallbacks", "c", "horizon dispatches degraded to "
                               "single-step decode"),
    ("sampled_tokens", "c", "tokens emitted by sampled (temperature>0) lanes"),
    ("stop_hits", "c", "requests finished by a stop sequence"),
    ("spec_k_capped", "c", "verify participations depth-capped by low "
                           "acceptance"),
    # multi-tenant LoRA serving (inference/adapters.py): pool-residency
    # traffic counters, incremented via the pool's stat hooks so there
    # is one source of truth
    ("adapter_hits", "c", "adapter acquisitions served pool-resident"),
    ("adapter_loads", "c", "adapter loads into the device pool"),
    ("adapter_evictions", "c", "refcount-zero adapters evicted (LRU)"),
    ("adapter_load_errors", "c", "requests retired state=error by a "
                                 "failed adapter load"),
    # host-tier mirrors (gauges set from the cache's own counters each
    # step, so the serving stats contract exposes them without a second
    # source of truth)
    ("host_blocks", "g", "KV blocks resident on the host-DRAM tier"),
    ("host_bytes", "g", "host-DRAM bytes held by spilled KV blocks"),
    ("host_spills", "g", "blocks spilled device->host (total)"),
    ("host_restores", "g", "blocks restored host->device (total)"),
    ("host_restore_failures", "g", "restores degraded to re-prefill "
                                   "(faults, corruption, dry free list)"),
)


class _StatsView(Mapping):
    """Read-only mapping over the registry-backed serving counters:
    the old ``stats`` dict's keys and values, minus mutability — writes
    go through the registry (``srv.metrics``), never through the view,
    so external code cannot skew the scheduler's bookkeeping."""

    def __init__(self, metrics: Dict[str, Any]):
        self._metrics = metrics

    def __getitem__(self, key):
        return self._metrics[key].value

    def __iter__(self):
        return iter(self._metrics)

    def __len__(self):
        return len(self._metrics)

    def __repr__(self):
        return repr(dict(self))


# ServeRequest fields dslint DS018 must NOT require to round-trip
# through snapshot_entry/from_snapshot — each is either derived on
# resubmit or meaningless on a fresh replica. Adding a field to
# ServeRequest without serializing it OR listing it here (with a
# reason) is a lint error: that is exactly how adapter_id, seed chains
# and cost footprints were silently lost before they were retrofitted.
SNAPSHOT_EPHEMERAL = frozenset({
    "n",                # expansion happens at submit; candidates snapshot
                        # individually, so a resumed request is always n=1
    "state",            # serialized for postmortems, but a resumed request
                        # must re-enter the scheduler as "queued"
    "token_times",      # scheduler-clock latency stamps; a fresh replica's
                        # clock makes them incomparable
    "submitted_at",     # ditto — resubmission re-stamps it
    "first_token_at",   # ditto
    "finished_at",      # pending requests by definition never finished
    "_admit_seq",       # admission order on the dead replica; the resuming
                        # scheduler assigns its own
    "_work",            # rebuilt from prompt + out at re-prefill
})


@dataclass
class ServeRequest:
    """One generation request. ``out`` accumulates generated token ids;
    ``token_times`` the scheduler-clock stamp of each emitted token (the
    bench derives per-token latency percentiles from these).
    ``deadline`` is an absolute scheduler-clock instant (same clock as
    ``submit``/``step``'s ``now``): once reached the request retires
    with ``state="timeout"``, keeping whatever it generated.

    Per-request sampling knobs (docs/SAMPLING.md): ``temperature`` /
    ``top_k`` / ``top_p`` / ``seed`` / ``repetition_penalty`` default to
    None = "use the engine-wide ctor default" — an explicit value wins.
    ``stop`` is a list of token-id sequences: generation finishes as
    soon as ``out`` ends with any of them (the matched stop tokens are
    KEPT in ``out``, so the resume/drain contract sees the true emitted
    stream). ``logprobs=True`` records each emitted token's
    log-probability under its sampling distribution in
    ``out_logprobs``. ``n>1`` expands at submit into ``n`` independent
    candidates (rids ``rid#1``..``rid#n-1`` plus the original) whose
    seeds derive from this request's seed via
    :func:`sampling.candidate_seed`.

    ``priority`` is an advisory class tag (``"interactive"`` /
    ``"batch"``; None = untagged) the engine itself ignores — the
    router's SLO controller sheds ``batch`` traffic first when
    admission tightens (docs/OBSERVABILITY.md)."""
    rid: Any
    prompt: np.ndarray
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    deadline: Optional[float] = None
    priority: Optional[str] = None
    # multi-tenant LoRA serving: which registered adapter decodes this
    # request (None = the base model; requires lora_serve on the
    # engine). An unloadable adapter retires the request with
    # state="error" — never wrong tokens (docs/ADAPTERS.md)
    adapter_id: Optional[str] = None
    temperature: Optional[float] = None
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    seed: Optional[int] = None
    repetition_penalty: Optional[float] = None
    stop: Optional[List[Sequence[int]]] = None
    logprobs: bool = False
    n: int = 1
    out: List[int] = field(default_factory=list)
    out_logprobs: List[float] = field(default_factory=list)
    state: str = "queued"      # queued | prefill | decode | handoff |
    #                            done | timeout | shed | error — handoff
    #                            = finished prefill parked on a
    #                            prefill-only replica awaiting migration
    token_times: List[float] = field(default_factory=list)
    submitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    evictions: int = 0
    # per-request cost footprint (telemetry/costs.py): FLOPs/HBM bytes/
    # dispatch counts per class + KV block-seconds. Plain data; rides
    # pending_snapshot() across drains so attribution survives a
    # replica death. Populated only while cost accounting is on.
    cost: Dict = field(default_factory=_new_footprint)
    _admit_seq: int = -1             # eviction picks the youngest
    _work: Optional[np.ndarray] = None   # prompt (+generated, on resume)

    @property
    def tokens(self) -> np.ndarray:
        """prompt + generated, the generate()-shaped result row."""
        return np.concatenate(
            [self.prompt, np.asarray(self.out, np.int32)])

    @classmethod
    def from_snapshot(cls, entry: Dict) -> "ServeRequest":
        """Rebuild a resumable request from a ``pending_snapshot()``
        entry — the cold-resume half of the drain contract: submitting
        the rebuilt request to a FRESH engine re-prefills prompt +
        already-emitted tokens, and decode continues from the exact
        pre-failure position. Greedy output is token-identical to an
        undisturbed run; a sampled request resumes its key chain exactly
        (the per-token key is a pure function of (seed, tokens emitted
        so far), so carrying seed + out IS the chain state —
        docs/SAMPLING.md). ``n`` is pinned to 1: candidate expansion
        already happened at the original submit."""
        return cls(
            rid=entry["rid"],
            prompt=np.asarray(entry["prompt"], np.int32),
            max_new_tokens=int(entry["max_new_tokens"]),
            eos_id=entry.get("eos_id"),
            deadline=entry.get("deadline"),
            priority=entry.get("priority"),
            adapter_id=entry.get("adapter_id"),
            temperature=entry.get("temperature"),
            top_k=entry.get("top_k"),
            top_p=entry.get("top_p"),
            seed=entry.get("seed"),
            repetition_penalty=entry.get("repetition_penalty"),
            stop=[list(s) for s in entry["stop"]]
            if entry.get("stop") else None,
            logprobs=bool(entry.get("logprobs", False)),
            n=1,
            out=[int(t) for t in entry.get("out", ())],
            out_logprobs=[float(x)
                          for x in entry.get("out_logprobs", ())],
            evictions=int(entry.get("evictions", 0)),
            cost=(dict(entry["cost"]) if entry.get("cost")
                  else _new_footprint()))


class DegradedError(RuntimeError):
    """The engine cannot meet its contract (hung step, non-drain) but
    the work it DID finish is intact: ``results`` maps rid ->
    prompt+generated for every retired request, ``finished`` holds the
    request objects, ``pending`` is a host-side snapshot of in-flight
    work (rid/state/tokens-generated/evictions), ``stats`` the engine
    counters at raise time. The scheduler state stays consistent — a
    caller may resubmit ``pending`` work or keep stepping."""

    def __init__(self, message: str, results: Optional[Dict] = None,
                 finished: Optional[List[ServeRequest]] = None,
                 pending: Optional[List[Dict]] = None,
                 stats: Optional[Dict] = None):
        super().__init__(message)
        self.results = results or {}
        self.finished = finished or []
        self.pending = pending or []
        self.stats = stats or {}


def snapshot_entry(req: ServeRequest, **extra) -> Dict:
    """One ``pending_snapshot()`` entry for ``req``: the resume-
    sufficient host-side view :meth:`ServeRequest.from_snapshot`
    round-trips, plus whatever position tags (``slot``/``queue_pos``)
    the caller adds. Token lists are copied — mutating the live request
    afterwards cannot skew an already-raised DegradedError."""
    entry = {"rid": req.rid, "state": req.state,
             "generated": len(req.out),
             "evictions": req.evictions,
             "prompt": [int(t) for t in req.prompt],
             "out": [int(t) for t in req.out],
             "max_new_tokens": req.max_new_tokens,
             "eos_id": req.eos_id,
             "deadline": req.deadline,
             "priority": req.priority,
             # a drained/resumed request re-attaches (or re-loads) its
             # adapter at the survivor's admission (docs/ADAPTERS.md)
             "adapter_id": req.adapter_id,
             # sampling state: the per-token key is a pure function of
             # (seed, len(out)), so these fields ARE the key-chain state
             # a drain/resume needs (docs/SAMPLING.md)
             "temperature": req.temperature,
             "top_k": req.top_k,
             "top_p": req.top_p,
             "seed": req.seed,
             "repetition_penalty": req.repetition_penalty,
             "stop": [[int(t) for t in s] for s in req.stop]
             if req.stop else None,
             "logprobs": req.logprobs,
             "out_logprobs": [float(x) for x in req.out_logprobs],
             # cost footprint rides the snapshot so a drained request
             # keeps its accrued attribution on the survivor replica
             "cost": json.loads(json.dumps(req.cost))}
    entry.update(extra)
    return entry


class ServingEngine:
    """Continuous-batching front end for an ``InferenceEngine``.

    ``num_blocks``/``hbm_budget_bytes`` bound the paged cache (the HBM
    watermark); ``num_slots`` bounds the decode batch; ``prefill_chunk``
    bounds how much prompt work one iteration may do (decode latency
    stays O(chunk) under long-prompt arrivals).

    Robustness knobs (all default to the pre-chaos behavior):

    - ``max_queue``: queue bound; a submit beyond it sheds the newcomer
      (``state="shed"``). None = unbounded.
    - ``max_evictions``: per-request preemption cap; at the cap a
      request is pinned against further eviction (storm guard).
    - ``step_time_budget_s`` / ``watchdog_grace``: decode-dispatch time
      budget; ``watchdog_grace`` consecutive over-budget steps raise
      :class:`DegradedError` with partial results. None disables.
    - ``max_retries`` / ``retry_backoff_s``: transient-device-error
      retry count and initial backoff (doubled per attempt, plus
      deterministic jitter from the fault injector's seeded rng).
    - ``faults``: a :class:`~deepspeed_tpu.utils.faults.FaultInjector`;
      defaults to the ambient one (env ``DS_FAULTS`` or installed).
    - ``prefix_cache``: shared-prefix KV reuse across requests
      (refcounted block sharing + radix index + copy-on-write). None
      defers to ``DS_PREFIX_CACHE`` (default off — the private-blocks
      allocator stays the bit-reference).
    - ``telemetry``: lifecycle tracing + spans + metrics registry
      (docs/OBSERVABILITY.md). True/False forces it, a
      :class:`~deepspeed_tpu.telemetry.Telemetry` instance is used
      as-is (share one across engines to aggregate), None defers to
      ``DS_TELEMETRY`` (default off — no-op plane, zero overhead).
    - ``spec_decode`` / ``spec_k`` / ``spec_draft``: speculative decode
      inside the batch (docs/SPECULATIVE.md) — each step a drafter
      proposes ``spec_k`` tokens per slot and ONE verify program scores
      all ``spec_k + 1`` positions; the accepted prefix advances the
      slot, the first reject rolls the cache back. temperature=0 slots
      accept by greedy-target agreement (bit-identical to spec-off
      greedy serving); sampled slots accept by rejection sampling
      (distribution-lossless, docs/SAMPLING.md).
      ``spec_decode`` None defers to ``DS_SPEC_DECODE`` (default off —
      plain one-token decode stays the bit-reference); ``spec_k`` None
      to ``DS_SPEC_K`` (default 4); ``spec_draft`` takes ``"ngram"``
      (prompt-lookup, default), a draft ``InferenceEngine``, or any
      ``propose(context, k)`` object.
    - ``spec_accept_floor`` / ``spec_adapt_warmup``: adaptive
      speculation depth — after ``spec_adapt_warmup`` verify
      participations, a slot whose acceptance EWMA is under the floor
      verifies only ONE draft token per step until its rate recovers
      (the verify program's static width never changes; floor<=0
      disables the cap).
    - ``temperature`` / ``top_k`` / ``seed``: engine-wide DEFAULTS for
      requests that leave their own sampling fields at None; a
      request's explicit knobs always win (docs/SAMPLING.md).
    - ``kv_quant``: int8 paged KV-cache blocks with per-block scales
      (docs/KV_QUANT.md) — ~2x decode slots at the same cache HBM.
      ``"int8"``/``"off"``; None defers to ``DS_KV_QUANT`` (default
      off — the unquantized pool stays the bit-reference; int8 is
      held to a documented greedy-match tolerance, not bit equality).
    - ``host_tier`` / ``host_budget_bytes``: host-DRAM second tier for
      refcount-zero cached prefix blocks (docs/KV_TIERING.md) — a
      low-watermark spill daemon rides each step's decode dispatch and
      a prefix hit on spilled links restores instead of re-prefilling.
      Requires ``prefix_cache``; restores/spills degrade to cold-miss
      re-prefill / plain eviction on any failure (CRC corruption,
      injected faults, budget exhaustion). None defers to
      ``DS_KV_HOST_TIER`` / ``DS_KV_HOST_BUDGET_MB`` (default off —
      the device-only cache stays the bit-reference).
      ``spill_watermark`` pins the free-list level below which the
      daemon spills (None = cache watermark + transfer batch).
    """

    def __init__(self, engine, *, num_slots: int = 4, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 hbm_budget_bytes: Optional[int] = None,
                 prefill_chunk: int = 64, temperature: float = 0.0,
                 top_k: int = 0, seed: int = 0,
                 decode_impl: Optional[str] = None,
                 prefix_cache: Optional[bool] = None,
                 max_queue: Optional[int] = None,
                 max_evictions: int = 8,
                 step_time_budget_s: Optional[float] = None,
                 watchdog_grace: int = 2,
                 max_retries: int = 3, retry_backoff_s: float = 0.02,
                 faults: Optional[faults_lib.FaultInjector] = None,
                 telemetry=None,
                 spec_decode: Optional[bool] = None,
                 spec_k: Optional[int] = None,
                 spec_draft=None,
                 spec_accept_floor: float = 0.125,
                 spec_adapt_warmup: int = 4,
                 kv_quant: Optional[str] = None,
                 host_tier: Optional[bool] = None,
                 host_budget_bytes: Optional[int] = None,
                 spill_watermark: Optional[int] = None,
                 lora_serve: Optional[bool] = None,
                 lora_pool_mb: Optional[float] = None,
                 lora_pool_blocks: Optional[int] = None,
                 lora_max_rank: Optional[int] = None,
                 lora_rank_block: Optional[int] = None,
                 decode_horizon: Optional[int] = None,
                 cost_accounting: Optional[bool] = None,
                 flight_recorder: Optional[bool] = None,
                 flight_dir: Optional[str] = None,
                 prefill_only: bool = False):
        if engine.is_encoder:
            raise ValueError("serving needs a causal decoder engine")
        self.engine = engine
        if isinstance(telemetry, (Telemetry, NoopTelemetry)):
            self.telemetry = telemetry
        elif resolve_telemetry(telemetry):
            self.telemetry = Telemetry()
        else:
            self.telemetry = NOOP
        # decode attention path ("pallas" flash-decode through the block
        # table | "gather" dense reference); defaults to the engine's
        # resolved choice so env/platform selection applies uniformly.
        # Pinned for the run: impl is a static jit arg, so ONE impl keeps
        # steady state at two compiled programs.
        if decode_impl is None:
            self.decode_impl = engine.decode_impl
        else:
            from deepspeed_tpu.ops.attention.paged import resolve_decode_impl
            self.decode_impl = resolve_decode_impl(decode_impl)
        self.faults = faults if faults is not None else faults_lib.active()
        self.prefix_cache = resolve_prefix_cache(prefix_cache)
        # int8 KV-cache pools with per-block scales (DS_KV_QUANT=int8):
        # resolved once here, pinned for the run — the cache then holds
        # scale pools beside k and v (``cache.pools``), and every
        # program compiles its int8 entry and no other
        self.kv_quant = resolve_kv_quant(kv_quant)
        # multi-tenant LoRA serving (inference/adapters.py): resolved
        # once here, pinned for the run — every dispatch then carries
        # the adapter operands, so a run compiles EITHER the base
        # entries or the adapter entries, never both (docs/ADAPTERS.md)
        self.lora_serve = resolve_lora_serve(lora_serve)
        # what cannot yet live with bounded window state raises here, by
        # name (prefix sharing, the host tier and int8 pools: the cache)
        for on, what in (
                (resolve_spec_decode(spec_decode),
                 "speculative decoding (spec_decode)"),
                (resolve_decode_horizon(decode_horizon) > 1,
                 "the fused decode horizon (decode_horizon)"),
                (self.lora_serve, "LoRA serving (lora_serve)")):
            if on:
                dialect.refuse(engine.cfg, what)
        # the engine's own jits of the three block copies (COW, and the
        # host tier's gather and scatter) are wired in when present:
        # each takes the cache's pools whole, scales included
        self.cache = PagedKVCache(
            engine.cfg, num_slots=num_slots, block_size=block_size,
            num_blocks=num_blocks, hbm_budget_bytes=hbm_budget_bytes,
            dtype=engine.dtype, max_seq_len=engine.max_seq_len,
            faults=self.faults, prefix_cache=self.prefix_cache,
            copy_fn=getattr(engine, "cow_blocks", None),
            kv_quant=self.kv_quant,
            host_tier=resolve_host_tier(host_tier),
            host_budget_bytes=host_budget_bytes,
            spill_watermark=spill_watermark,
            gather_fn=getattr(engine, "gather_blocks", None),
            scatter_fn=getattr(engine, "scatter_block", None),
            tracer=self.telemetry.tracer
            if self.telemetry.enabled else None)
        # the EFFECTIVE switch: the cache gates the tier on the prefix
        # index existing (only indexed blocks ever spill)
        self.host_tier = self.cache.host_tier
        if self.cache.dialect.state is not None and self.telemetry.enabled \
                and hasattr(engine.cfg, "moe_k"):
            # expert-layer counters ride with the K state, on the device
            # (read_expert_counters pulls them); a dense model beside a
            # recurrent state has none
            from deepspeed_tpu.moe.expert_share import stat_fields
            self.cache.k = self.cache.k._replace(
                stats=jnp.zeros((2, len(stat_fields(engine.cfg))),
                                jnp.int32))
        mesh = getattr(engine, "mesh", None)
        if mesh is not None:
            # place the fresh pools exactly where the jitted programs
            # will put them (replicated over the engine mesh): a first
            # prefill call with differently-placed pools keys a second,
            # single-use executable — one whole wasted XLA compile at
            # cold start (caught by test_serving_compile_count_contract)
            from jax.sharding import NamedSharding, PartitionSpec
            pool_sh = NamedSharding(mesh, PartitionSpec())
            self.cache.pools = jax.device_put(self.cache.pools, pool_sh)
        # compile the COW copy program now (after pool placement, so the
        # warmed executable matches steady-state shardings): the first
        # mid-block divergence must not add a compile inside the
        # CompileWatch-pinned steady state
        self.cache.warm_cow()
        # same contract for the host-tier transfer programs: the first
        # spill/restore must not compile inside the pinned steady state
        self.cache.warm_host_tier()
        # disaggregated prefill role (docs/ROBUSTNESS.md): a prefill-only
        # replica runs chunked prefill, emits the FIRST token (TTFT is
        # stamped where the prefill ran), then parks the request in
        # state="handoff" for the router to migrate its KV to a decode
        # replica — it never runs a decode step for it. Plain flag, no
        # program change: the decode executables stay compiled/warm, so
        # flipping a replica's role never recompiles.
        self.prefill_only = bool(prefill_only)
        self.num_slots = num_slots
        self.prefill_chunk = int(prefill_chunk)
        self.temperature = temperature
        self.top_k = top_k
        self.max_queue = max_queue
        self.max_evictions = int(max_evictions)
        self.step_time_budget_s = step_time_budget_s
        self.watchdog_grace = max(1, int(watchdog_grace))
        self.max_retries = int(max_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        # speculative decode: drafter + chunk length resolved once at
        # construction (spec_k is baked into the verify program's static
        # G = spec_k + 1 token dimension, so it cannot change per step)
        self.spec_decode = resolve_spec_decode(spec_decode)
        self.spec_k = resolve_spec_k(spec_k)
        self.draft = make_draft(spec_draft) if self.spec_decode else None
        # adaptive speculation depth: a slot whose acceptance EWMA sinks
        # under ``spec_accept_floor`` (after ``spec_adapt_warmup``
        # verify participations) caps its accepted prefix at 1 draft
        # token, so adversarial low-accept traffic stops paying verify
        # rollbacks for depth it never uses (floor<=0 disables)
        self.spec_accept_floor = float(spec_accept_floor)
        self.spec_adapt_warmup = int(spec_adapt_warmup)
        self._accept_ewma = np.ones(num_slots, np.float64)
        self._spec_obs = np.zeros(num_slots, np.int64)
        # fused multi-step decode horizon (docs/MULTISTEP.md): N decode
        # iterations per dispatch, resolved once and pinned — N is a
        # static dimension of the horizon programs, so one run compiles
        # exactly one horizon family (N=1 keeps the single-step
        # bit-reference program and never compiles the family at all).
        # With spec_decode on, the verify chunk is already the
        # multi-token step and takes precedence
        self.decode_horizon = resolve_decode_horizon(decode_horizon)
        # horizon-aware scheduler clock: the deadline clock ticks once
        # per EMITTED token (not per step), so step-clock deadlines and
        # ttft/tpot keep their one-token-per-tick meaning at N > 1.
        # _horizon_ticks = ticks the last decode phase consumed;
        # last_step_span exposes it to external step-unit drivers
        # (tools/load_gen.drive); token_time_unit is the per-token stamp
        # spacing such a driver announces (0.0 = wall-clock caller: all
        # of a horizon's tokens stamp at dispatch time)
        self._horizon_ticks = 1
        self._token_tick = 0.0
        self._kv_tokens = 0     # the serve.decode span's, telemetry on
        # beside it on a sampled step: (kv_steps, idle_tiles), the grid
        # steps the live slots run and the ones the plan left out
        self._tiles = None
        self.last_step_span = 1.0
        self.token_time_unit = 0.0
        # the account of the host time between two dispatches
        # (_account_gap, telemetry on): the stamp at which the previous
        # dispatch's wait returned and its site, the seconds outside
        # every serve.step since then, the end of the last serve.step,
        # and whether the engine held no request at some moment since
        self._gap_prev_t: Optional[float] = None
        self._gap_prev_site = ""
        self._gap_caller = 0.0
        self._gap_step_t1: Optional[float] = None
        self._gap_empty = True
        # what the plane took of the host itself since the last dispatch
        # (self_us / self_parts on serve.dispatch, _account_gap): seconds
        # on the tracer's clock stamped around the step's histogram
        # observes, its span counts and the sampled gauges; the spans'
        # own enter and exit are the tracer's (span_self), the charges
        # the accountant's (self_s)
        self._self_hist = self._self_counts = self._self_gauges = 0.0
        self._clock = self.telemetry.tracer._clock \
            if self.telemetry.enabled else None
        # per-request sampling: engine-wide ctor knobs are DEFAULTS a
        # request's own fields override (sampling.resolve_params); the
        # resolved knobs live as slot-indexed arrays the fused sampler
        # reads as data, so greedy/sampled mixes share one program
        self.seed = int(seed)
        self.sampler = sampling.SlotSamplerState(num_slots,
                                                 engine.cfg.vocab_size)
        self._slot_params: List[Optional[sampling.SamplingParams]] = \
            [None] * num_slots
        self.queue: deque = deque()
        self.slots: List[Optional[ServeRequest]] = [None] * num_slots
        self.finished: List[ServeRequest] = []
        self._progress = np.zeros((num_slots,), np.int64)  # prefilled toks
        # what a step needs of every slot's request, as arrays beside
        # cache.lengths, so that a step tests and feeds all slots at once.
        # Written by _seat and _vacate, the one pair through which a slot
        # or its request's state changes, and per token by the two emit
        # paths: a request's deadline (inf: none), eos (-1: none) and
        # max_new_tokens; which phase holds the slot; for a decoding slot
        # its last emitted token and its generated count (0 elsewhere:
        # they are the decode program's operands); and which decoding
        # slots emit through _emit_sampled, one by one (_slow_emit)
        self._held = np.zeros((num_slots,), bool)
        self._prefilling = np.zeros((num_slots,), bool)
        self._decoding = np.zeros((num_slots,), bool)
        self._slow = np.zeros((num_slots,), bool)
        self._deadline = np.full((num_slots,), np.inf)
        self._eos = np.full((num_slots,), -1, np.int64)
        self._max_new = np.zeros((num_slots,), np.int64)
        self._last_tok = np.zeros((num_slots,), np.int32)
        self._gen = np.zeros((num_slots,), np.int32)
        # telemetry on: when a decoding slot's last token was emitted
        # (its request's token_times[-1]), for a step's TPOT in one
        # gather; written by _seat and the two emit paths
        self._tok_t = np.zeros((num_slots,), np.float64) \
            if self.telemetry.enabled else None
        self._grown = 0         # the serve.decode span's, as _kv_tokens
        self._admit_counter = 0
        self._over_budget = 0            # consecutive watchdog strikes
        self._watchdog_msg: Optional[str] = None
        # the deadline clock is its OWN monotone counter (one tick per
        # step): stats["steps"] used to double as it, which let a stats
        # mutation skew every relative deadline — now stats are a
        # read-only view and the clock is private
        self._step_clock = 0
        # stats route through a metrics registry (the telemetry one
        # when enabled, else a private one — the counters must stay
        # live either way since they ARE the public stats contract)
        self.metrics = (self.telemetry.registry if self.telemetry.enabled
                        else MetricsRegistry())
        self._stat = {}
        for key, kind, help_ in _STAT_FIELDS:
            make = (self.metrics.counter if kind == "c"
                    else self.metrics.gauge)
            self._stat[key] = make(f"serving_{key}", help_)
        self.stats = _StatsView(self._stat)
        # beside decode_steps and occupancy_sum: slow / occupancy_sum is
        # the share of slot-steps that took the per-slot emit
        self._c_emit_slow = self.metrics.counter(
            "serving_emit_slow_slots_total",
            "slot-steps of plain decode steps that emitted through the "
            "per-slot path (_emit_sampled: the request has stop sequences, "
            "asked for logprobs or carries a repetition penalty); every "
            "other live slot of a step is emitted by array operations")
        # a prefill chunk's latent layers run the engine's decode_impl
        self._mla_tiles = self._stat[
            "mla_prefill_tiles_kernel_total" if engine.decode_impl == "pallas"
            else "mla_prefill_tiles_plain_total"]
        # a model with a per-slot recurrent state (inference/linear.py,
        # whichever rule writes it): how often a slot's state was started
        # from zeros, and how often that was a preempted request's replay
        # rebuilding it
        self._state_resets = self._state_replays = None
        if self.cache.recurrent_state_bytes:
            self._state_resets = self.metrics.counter(
                "serving_state_resets",
                "prefill chunks at position 0 of a model with a per-slot "
                "recurrent state (linear-attention or state-space layers): "
                "the slot's state and convolution tail start from zeros "
                "(nothing is cleared: the chunk does not read what the "
                "slot held)")
            self._state_replays = self.metrics.counter(
                "serving_state_replays",
                "of those, re-prefills of a preempted request (prompt + "
                "generated from position 0): the replay rebuilds the "
                "state, which no block holds")
        if self.telemetry.enabled:
            reg = self.metrics
            self._h_ttft = reg.histogram(
                "serving_ttft", "time to first token (scheduler clock "
                "units: seconds under wall_clock, steps otherwise)")
            self._h_tpot = reg.histogram(
                "serving_tpot",
                "per-output-token latency (scheduler clock units)")
            self._h_qwait = reg.histogram(
                "serving_queue_wait",
                "enqueue-to-admit wait (scheduler clock units)")
            self._h_occ = reg.histogram(
                "serving_batch_occupancy", "decoding slots per step",
                buckets=tuple(float(i) for i in range(num_slots + 1)))
            # wall seconds of every step, and of its four phases on the
            # sampled steps, from the step's spans (serve.step and its
            # children)
            self._h_step = reg.histogram(
                "serving_step_s", help="total wall seconds per step",
                buckets=_PHASE_BUCKETS)
            self._h_phase = {
                ph: reg.histogram(
                    f"serving_step_{ph}_s",
                    help=f"wall seconds per step in the {ph} phase",
                    buckets=_PHASE_BUCKETS)
                for ph in _STEP_PHASES}
            # the host seconds between two dispatches and their parts,
            # from the dispatch's and the step's spans (_account_gap)
            self._h_gap = reg.histogram(
                "serving_dispatch_gap_s",
                help="host seconds in which no program was enqueued: from "
                "the return of one dispatch's wait to the return of the "
                "next one's enqueue (a gap after an empty engine left out)",
                buckets=_PHASE_BUCKETS)
            self._c_gap_sched = reg.counter(
                "serving_gap_sched_seconds_total",
                "seconds of those gaps inside serve.step and outside a "
                "dispatch: the scheduler's own")
            self._c_gap_caller = reg.counter(
                "serving_gap_caller_seconds_total",
                "seconds of those gaps outside every serve.step: the loop "
                "that calls step()")
            self._c_gap_enqueue = reg.counter(
                "serving_gap_enqueue_seconds_total",
                "seconds of those gaps inside serve.dispatch.enqueue")
            self._c_wait = reg.counter(
                "serving_dispatch_wait_seconds_total",
                "seconds blocked in serve.dispatch.wait, every dispatch")
            self._c_empty = reg.counter(
                "serving_engine_empty_seconds_total",
                "seconds of the gaps that follow an empty engine: pauses, "
                "in no other gap metric")
            self._c_self = reg.counter(
                "serving_telemetry_self_seconds_total",
                "host seconds the telemetry plane stamped on its own "
                "account (span enter and exit, cost charges, histogram "
                "observes, span counts, sampled gauges; what ran under a "
                "running program included): over the sum of the three "
                "serving_gap_*_seconds_total, the plane's share of the "
                "host time between dispatches")
            self._g_held = reg.gauge(
                "serving_hbm_blocks_held", "pool blocks with refcount > 0")
            self._g_cached = reg.gauge(
                "serving_hbm_blocks_cached",
                "refcount-0 blocks kept by the prefix index")
            self._g_free = reg.gauge(
                "serving_hbm_blocks_free", "free-list blocks")
            self._g_hit_rate = reg.gauge(
                "serving_prefix_hit_rate", "prefix hits / admissions")
            self._h_accept = reg.histogram(
                "serving_spec_accept_rate",
                "per-verify-step draft acceptance rate",
                buckets=RATE_BUCKETS)
            self._h_tps = reg.histogram(
                "serving_spec_tokens_per_step",
                "tokens emitted per live slot per verify step",
                buckets=tuple(float(i)
                              for i in range(1, self.spec_k + 2)))
            # multi-step decode plane (docs/MULTISTEP.md): realized
            # per-slot horizon utilization + the run's configured N
            self._h_horizon = reg.histogram(
                "serving_horizon_tokens",
                "tokens emitted per slot per fused multi-step decode "
                "dispatch",
                buckets=tuple(float(i)
                              for i in range(1, self.decode_horizon + 2))) \
                if self.decode_horizon > 1 else None
            self._g_horizon = reg.gauge(
                "decode_horizon",
                "fused decode iterations per dispatch (static per run; "
                "1 = single-step bit-reference)")
            self._g_horizon.set(float(self.decode_horizon))
            self._h_temp = reg.histogram(
                "serving_request_temperature",
                "resolved per-request sampling temperature at admission "
                "(0 = greedy)",
                buckets=TEMP_BUCKETS)
            # KV-pool shape of THIS run (static per run, gauges so the
            # Prometheus text path exports them next to the block
            # gauges): bytes/token includes the amortized per-block
            # scale overhead under int8
            self._g_kv_bpt = reg.gauge(
                "kv_cache_bytes_per_token",
                "KV pool bytes per cached token (all layers, K+V, "
                "including per-block scale overhead when quantized)")
            self._g_kv_bpt.set(
                self.cache.bytes_per_token
                + self.cache.scale_bytes_per_block / self.cache.block_size)
            self._g_kv_dtype = reg.gauge(
                "kv_pool_dtype", "KV pool element width in bits "
                "(8 = int8 quantized, 16 = bf16, 32 = f32)")
            self._g_kv_dtype.set(self.cache.pool_dtype.itemsize * 8)
            self.sampler.on_mask_upload = reg.counter(
                "sampler_mask_uploads",
                "uploads of the sampler's seen mask to the device: one "
                "when a penalized request marks or clears its row, none "
                "for greedy or unpenalized traffic").inc
            # two kinds of KV state (inference/hybrid.py): the paged pool
            # of the full layers and the window layers' per-slot rings
            reg.gauge("kv_full_pool_bytes",
                      "device bytes of the paged KV pool (full-attention "
                      "layers, K+V, trash block included)").set(
                (self.cache.num_blocks * self.cache.block_size
                 * self.cache.bytes_per_token))
            reg.gauge("kv_window_state_bytes",
                      "device bytes of the sliding-window layers' per-slot "
                      "rings (K+V, all slots; 0 without such layers)").set(
                self.cache.window_bytes)
            reg.gauge("kv_window_ring_rows_allocated",
                      "rows (token places) of the window layers' rings, "
                      "all slots and window layers; 0 without such "
                      "layers").set(self.cache.ring_rows_allocated)
            self._g_ring_used = reg.gauge(
                "kv_window_ring_rows_used",
                "rows of the window layers' rings that hold a token a "
                "query can still see: min(length, window) a seated slot "
                "a window layer (sampled)")
            # a latent pool, per-slot tails, a recurrent state
            self.cache.dialect.gauges(reg, self.cache)
            self._h_kv_err = reg.histogram(
                "serving_kv_quant_error",
                "sampled upper bound on the max-abs KV dequantization "
                "error (half the hottest block's quantization step)",
                buckets=(1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3,
                         1e-2, 2.5e-2, 5e-2, 1e-1, 2.5e-1)) \
                if self.cache.quantized else None
            # host-tier plane (docs/KV_TIERING.md): DRAM footprint gauge
            # plus per-restore latency histogram — restores sit on the
            # admission path, so their tail IS the warm-hit TTFT tax
            self._g_host_bytes = reg.gauge(
                "kv_host_tier_bytes",
                "host-DRAM bytes held by spilled KV blocks") \
                if self.host_tier else None
            self._h_host_restore = reg.histogram(
                "kv_host_restore_ms",
                "per-block host->device restore wall time (CRC verify "
                "+ H2D copy + scatter dispatch, ms)",
                buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                         25.0, 50.0, 100.0)) \
                if self.host_tier else None
            # adapter plane (docs/ADAPTERS.md): pool residency + size,
            # refreshed by the pool's stat hooks below
            self._g_lora_active = reg.gauge(
                "lora_active_adapters",
                "LoRA adapters resident in the device pool") \
                if self.lora_serve else None
            self._g_lora_pool = reg.gauge(
                "lora_pool_bytes",
                "device bytes reserved by the paged adapter pool") \
                if self.lora_serve else None

            def _on_fault(site: str, kind: str, visit: int) -> None:
                # injected faults land in the SAME timeline as the
                # request lifecycle, stamped with the scheduler step at
                # fire time — a chaos run replays as one trace
                self.telemetry.tracer.event(
                    "fault", step=self._step_clock,
                    site=site, kind=kind, visit=visit)

            self._fault_listener = _on_fault
            self.faults.add_listener(self._fault_listener)
        else:
            self._h_ttft = self._h_tpot = self._h_qwait = self._h_occ = None
            self._h_step = self._h_phase = None
            self._h_accept = self._h_tps = self._h_temp = None
            self._h_horizon = self._g_horizon = None
            self._h_kv_err = None
            self._g_host_bytes = self._h_host_restore = None
            self._g_lora_active = self._g_lora_pool = None
            self._fault_listener = None
        # the paged adapter pool + per-slot adapter-table rows: row j
        # holds the block ids the compiled programs gather slot j's
        # adapter factors through (all zeros = base-only: trash block 0
        # gathers exact zeros, keeping base-only slots bit-identical to
        # the pre-subsystem stream)
        if self.lora_serve:
            self.adapters = AdapterPool(
                engine, pool_mb=lora_pool_mb, pool_blocks=lora_pool_blocks,
                max_rank=lora_max_rank, rank_block=lora_rank_block,
                faults=self.faults,
                tracer=(self.telemetry.tracer if self.telemetry.enabled
                        else None),
                hooks={"on_hit": self._stat["adapter_hits"].inc,
                       "on_load": self._on_adapter_load,
                       "on_evict": self._on_adapter_evict})
            self._slot_arows = np.zeros(
                (num_slots, self.adapters.blocks_per_adapter), np.int32)
            if self._g_lora_pool is not None:
                self._g_lora_pool.set(self.adapters.pool_bytes)
        else:
            self.adapters = None
            self._slot_arows = None
        # cost-accounting plane (telemetry/costs.py, docs/OBSERVABILITY
        # .md): exact integer FLOPs/HBM-bytes/block-seconds attribution
        # per dispatch class, request and tenant. DS_TELEMETRY=on
        # implies it; DS_COST_ACCOUNTING=on enables it standalone.
        # Charges are host-int arithmetic only — no device work, no new
        # programs, and the off path is the usual constant no-op twin
        if self.telemetry.enabled \
                or resolve_flag("DS_COST_ACCOUNTING", cost_accounting):
            kv_tok = int(self.cache.bytes_per_token)
            block_bytes = (kv_tok * self.cache.block_size
                           + int(self.cache.scale_bytes_per_block))
            try:
                param_itemsize = int(np.dtype(engine.dtype).itemsize)
            except TypeError:
                param_itemsize = 2
            self.costs = CostAccountant(
                engine.cfg, kv_tok, block_bytes, param_itemsize,
                registry=self.metrics, slots=self.slots,
                clock=self._clock or time.perf_counter)
            self.cost_registry = ProgramCostRegistry()
            self.cost_registry.populate(engine, cache=self.cache)
            if self.telemetry.enabled:
                self.cost_registry.export_gauges(self.metrics)
                # the first dispatch of each serving program (warm-up)
                # adds its provenance table to the registry
                engine.provenance = self.cost_registry
        else:
            self.costs = NOOP_COSTS
            self.cost_registry = None
        # flight recorder (telemetry/flight.py): armed when
        # DS_FLIGHT_RECORDER=on — a DegradedError writes a versioned,
        # CRC-stamped postmortem artifact tools/postmortem.py can
        # analyze with zero live objects
        if resolve_flag("DS_FLIGHT_RECORDER", flight_recorder):
            self.flight = FlightRecorder(
                outdir=flight_dir or (resolve_flag("DS_FLIGHT_DIR")
                                      or None),
                sections=self._flight_sections(), label="serving")
        else:
            self.flight = NOOP_FLIGHT

    def _flight_sections(self) -> Dict:
        """Postmortem section providers — called only at dump time."""
        return {
            "tracer": lambda: [list(r)
                               for r in self.telemetry.tracer.records()],
            "metrics": lambda: self.metrics.snapshot(),
            "windows": lambda: {n: h.window_summary()
                                for n, h in
                                self.metrics._histograms.items()},
            "stats": lambda: dict(self.stats),
            "faults": lambda: [list(f) for f in self.faults.fired],
            "flags": lambda: {n: resolve_flag(n) for n in flag_names()},
            "programs": lambda: (self.cost_registry.to_json()
                                 if self.cost_registry else {}),
            "costs": lambda: self.costs.snapshot(),
            "requests": self._flight_requests,
        }

    def _flight_requests(self) -> List[Dict]:
        """Per-request postmortem rows: every finished request plus the
        in-flight set, each with its lifecycle state and cost
        footprint."""
        self.costs.flush()      # a view: seated requests' cost current
        rows = []
        for req in self.finished:
            rows.append({"rid": req.rid, "state": req.state,
                         "generated": len(req.out),
                         "adapter_id": req.adapter_id,
                         "evictions": req.evictions,
                         "cost": req.cost})
        for slot, req in enumerate(self.slots):
            if req is not None:
                rows.append({"rid": req.rid, "state": req.state,
                             "slot": slot, "generated": len(req.out),
                             "adapter_id": req.adapter_id,
                             "evictions": req.evictions,
                             "cost": req.cost})
        for pos, req in enumerate(self.queue):
            rows.append({"rid": req.rid, "state": req.state,
                         "queue_pos": pos, "generated": len(req.out),
                         "adapter_id": req.adapter_id,
                         "evictions": req.evictions,
                         "cost": req.cost})
        return rows

    def _on_adapter_load(self) -> None:
        self._stat["adapter_loads"].inc()
        if self._g_lora_active is not None:
            self._g_lora_active.set(self.adapters.active_adapters)

    def _on_adapter_evict(self) -> None:
        self._stat["adapter_evictions"].inc()
        if self._g_lora_active is not None:
            self._g_lora_active.set(self.adapters.active_adapters)

    def register_adapter(self, adapter_id: str, source) -> None:
        """Stage a ``runtime/lora.py`` adapter export for serving under
        ``adapter_id`` (requires ``lora_serve``); device residency is
        deferred to the first admission that names it."""
        if self.adapters is None:
            raise ValueError("register_adapter requires lora_serve=True "
                             "(DS_LORA_SERVE=on)")
        self.adapters.register(adapter_id, source)

    def _lora_args(self, slot: Optional[int] = None):
        """The engine's ``lora=`` operand for the whole batch (or one
        prefill slot). None when the subsystem is off — the base
        programs stay the only ones ever traced."""
        if self.adapters is None:
            return None
        rows = self._slot_arows if slot is None else self._slot_arows[slot]
        return self.adapters.lora_args(rows)

    # -- API -----------------------------------------------------------
    def submit(self, req: ServeRequest, now: float = 0.0) -> bool:
        """Enqueue ``req``. Returns False when the bounded queue is full
        and the request was shed instead (``state="shed"``, recorded in
        ``finished`` so the caller sees exactly one terminal state per
        request). Malformed requests still raise ValueError."""
        total = len(req.prompt) + req.max_new_tokens
        if total > self.engine.max_seq_len:
            raise ValueError(
                f"request {req.rid}: prompt {len(req.prompt)} + "
                f"max_new {req.max_new_tokens} exceeds max_seq_len "
                f"{self.engine.max_seq_len}")
        if self.cache.blocks_for(total) > self.cache.num_blocks - 1:
            raise ValueError(
                f"request {req.rid} needs more blocks than the whole pool")
        # fail fast on malformed sampling knobs (resolve_params
        # validates the resolved bundle) — and resolve once here so the
        # n>1 expansion below derives candidate seeds from the SAME
        # seed admission will use
        params = sampling.resolve_params(req, self.temperature,
                                         self.top_k, self.seed)
        if req.n < 1:
            raise ValueError(f"request {req.rid}: n must be >= 1, "
                             f"got {req.n}")
        if req.n > 1:
            # expand into n independent candidates: the original keeps
            # its rid as candidate 0, clones get rid#i and a
            # SeedSequence-derived seed. n is pinned back to 1 on every
            # piece so a drain/resume resubmit never re-expands.
            n, req.n = req.n, 1
            ok = self.submit(req, now=now)
            for i in range(1, n):
                clone = ServeRequest(
                    rid=f"{req.rid}#{i}", prompt=req.prompt,
                    max_new_tokens=req.max_new_tokens, eos_id=req.eos_id,
                    deadline=req.deadline, adapter_id=req.adapter_id,
                    temperature=req.temperature,
                    top_k=req.top_k, top_p=req.top_p,
                    seed=sampling.candidate_seed(params.seed, i),
                    repetition_penalty=req.repetition_penalty,
                    stop=req.stop, logprobs=req.logprobs, n=1)
                ok = self.submit(clone, now=now) and ok
            return ok
        req.submitted_at = now
        # resume-aware working prompt: a request rebuilt from a
        # pending snapshot (out non-empty) re-prefills prompt+partial —
        # the same recompute-on-resume contract _preempt uses — so a
        # drained request continues token-identically on a fresh engine
        req._work = np.asarray(req.tokens if req.out else req.prompt,
                               np.int32)
        self.telemetry.tracer.event("enqueue", rid=req.rid,
                                    step=self._step_clock,
                                    queue_len=len(self.queue))
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            # reject-newest: accepted work keeps its latency budget; the
            # newcomer gets an immediate, explicit answer instead of an
            # unbounded queue wait
            req.state = "shed"
            req.finished_at = now
            self.finished.append(req)
            self._stat["shed"].inc()
            self.telemetry.tracer.event("finish", rid=req.rid,
                                        step=self._step_clock,
                                        state="shed", generated=0)
            self._update_backpressure()
            logger.warning(f"serving: shed request {req.rid} "
                           f"(queue full at {self.max_queue})")
            return False
        self.queue.append(req)
        self._update_backpressure()
        return True

    @property
    def busy(self) -> bool:
        return bool(self.queue) or bool(self._held.any())

    def step(self, now: Optional[float] = None) -> int:
        """One scheduler iteration: expire, admit, prefill chunks,
        decode. Returns the number of decoding slots this iteration
        (the occupancy sample). Raises :class:`DegradedError` when the
        step watchdog trips (state stays consistent — every token
        produced so far, including this step's, is recorded)."""
        if now is None:
            now = float(self._step_clock)
            # internal step-clock mode: one tick per emitted token, so
            # a horizon's tokens stamp at now, now+1, ... exactly as
            # the N=1 loop would have stamped them
            self._token_tick = 1.0
        else:
            self._token_tick = float(self.token_time_unit)
        self._horizon_ticks = 1
        tracer = self.telemetry.tracer
        clock = self._step_clock
        with tracer.span("serve.step", step=clock,
                         queue=len(self.queue)) as s_step:
            if self._gap_step_t1 is not None:
                # what the caller's loop took between two steps
                self._gap_caller += s_step.t0 - self._gap_step_t1
            with tracer.span("serve.expire", step=clock) as s_expire:
                c0 = self._span_counts()
                self._expire(now)
                c1 = self._span_counts()
                if c1:
                    s_expire.set(expired=c1[0] - c0[0],
                                 blocks_freed=c0[3] - c1[3])
            with tracer.span("serve.admit", step=clock) as s_admit:
                self._admit(now)
                c2 = self._span_counts()
                if c2:
                    s_admit.set(admitted=c2[1] - c1[1],
                                blocks_allocated=c2[3] - c1[3])
            self._prefill_step(now)
            with tracer.span("serve.decode", step=clock) as s_decode:
                c3 = self._span_counts()
                occ = self._decode_step(now)
                c4 = self._span_counts()
                if c4:
                    s_decode.set(live=occ, blocks=c4[3], grown=self._grown,
                                 state_slots=occ if
                                 self.cache.recurrent_state_bytes else 0,
                                 kv_tokens=self._kv_tokens,
                                 evicted=c4[2] - c3[2])
                    if self._tiles is not None:     # a sampled step
                        s_decode.set(kv_steps=self._tiles[0],
                                     idle_tiles=self._tiles[1])
            with tracer.span("serve.spill", step=clock) as s_spill:
                self._spill_step()
            with tracer.span("serve.bookkeep", step=clock):
                self._bookkeep(occ, clock)
        if self._h_step is not None:
            # the phases tile the step: admission ends with serve.admit,
            # prefill with the start of serve.decode, decode (the
            # host-tier tick with it) with serve.spill
            t = self._clock()
            self._h_step.observe(s_step.dur)
            if clock % self.telemetry.sample_every == 0:
                # the four phases on the sampled steps (serving_step_s
                # and the spans hold every step's stamps)
                self._h_phase["admission"].observe(s_admit.t1 - s_step.t0)
                self._h_phase["prefill"].observe(s_decode.t0 - s_admit.t1)
                self._h_phase["decode"].observe(s_spill.t1 - s_decode.t0)
                self._h_phase["bookkeeping"].observe(
                    s_step.t1 - s_spill.t1)
            self._gap_step_t1 = s_step.t1
            if not self.busy:
                self._gap_empty = True
            self._self_hist += self._clock() - t
        if self._watchdog_msg is not None:
            msg, self._watchdog_msg = self._watchdog_msg, None
            self._over_budget = 0
            self.telemetry.tracer.event("degraded", step=self._step_clock,
                                        message=msg)
            raise self._degraded(msg)
        return occ

    def _span_counts(self):
        """(timeouts, admitted, evictions, used blocks) for the counts on
        a step's spans; () with telemetry off."""
        if not self.telemetry.enabled:
            return ()
        t = self._clock()
        st = self._stat
        counts = (st["timeouts"].value, st["admitted"].value,
                  st["evictions"].value, self.cache.used_blocks)
        self._self_counts += self._clock() - t
        return counts

    def _bookkeep(self, occ: int, clock: int) -> None:
        """Everything ``step`` does after the host-tier tick: the
        deadline clock, KV residency charges, the stats, backpressure
        and (every ``sample_every``-th step) the sampled gauges."""
        # the deadline clock advances one tick per emitted token: a
        # horizon-N decode that produced p tokens consumed p ticks, so
        # relative deadlines keep their token-count meaning at N > 1
        # (N=1 keeps _horizon_ticks at 1 — bit-identical clocking)
        self._step_clock += self._horizon_ticks
        self.last_step_span = float(self._horizon_ticks)
        if self.costs.enabled:
            # KV residency integrates at horizon boundaries: every slot
            # holder is billed its block count x the ticks this step
            # consumed (scheduler-clock units; seconds under wall_clock):
            # one call over the seated mask, blocks_for of every length
            self.costs.charge_block_seconds(
                self._held, self.cache.lengths, self.cache.block_size,
                self._horizon_ticks)
        self._stat["steps"].inc()
        self._stat["occupancy_sum"].inc(occ)
        peak = self._stat["peak_occupancy"]
        peak.set(max(peak.value, occ))
        self._update_backpressure()
        if self._h_occ is not None:
            t = self._clock()
            self._h_occ.observe(occ)
            self._self_hist += self._clock() - t
            if clock % self.telemetry.sample_every == 0:
                self._sample_gauges()

    def run(self, requests=None, max_steps: int = 1_000_000,
            wall_clock: bool = False) -> Dict[Any, np.ndarray]:
        """Drain: submit ``requests`` (if given) and step until idle.
        Returns {rid: prompt+generated} for every retired request (the
        terminal state lives on the request object). Submissions are
        stamped with the SAME clock the step loop uses, so
        ``submitted_at``-based latency percentiles are meaningful under
        ``wall_clock=True``. A non-drain raises :class:`DegradedError`
        with everything finished so far attached instead of discarding
        it."""
        for r in (requests or []):
            self.submit(r, now=time.perf_counter() if wall_clock else 0.0)
        steps = 0
        while self.busy:
            self.step(time.perf_counter() if wall_clock else None)
            steps += 1
            if steps > max_steps:
                raise self._degraded(
                    f"serving did not drain in {max_steps} steps "
                    f"(queue {len(self.queue)})")
        return {r.rid: r.tokens for r in self.finished}

    def pending_snapshot(self, release: bool = False) -> List[Dict]:
        """Host-side view of in-flight work (attached to
        :class:`DegradedError`): one entry per slot/queue request.

        Entries carry everything :meth:`ServeRequest.from_snapshot`
        needs to round-trip into a *fresh* engine (prompt, emitted
        tokens, budget, eos, deadline) — host-side copies, decoupled
        from the live request objects. The default is NON-destructive:
        the engine keeps its slots/queue, so a watchdog-degraded caller
        may simply keep stepping. ``release=True`` is the declared-dead
        path (the router's drain): every slot's blocks — including
        prefix-cache pins — go back to the pool and the queue empties,
        so the snapshot is the only remaining owner of the work."""
        self.costs.flush()      # a view: seated requests' cost current
        snap = []
        for slot, r in enumerate(self.slots):
            if r is not None:
                snap.append(snapshot_entry(r, slot=slot))
        for pos, r in enumerate(self.queue):
            snap.append(snapshot_entry(r, queue_pos=pos))
        if release:
            # drain/retire contract (docs/KV_TIERING.md): in-flight
            # spills settle BEFORE any slot releases — a mid-transfer
            # block must be releasable like any other, and the snapshot
            # path must never race a harvest. Parked migration landings
            # settle with the same discipline (docs/ROBUSTNESS.md):
            # their requests re-prefill cold on a survivor
            self.cache.abort_transfers()
            self.cache.abort_parked()
            for slot, r in enumerate(self.slots):
                if r is not None:
                    self._vacate(slot, r)
            self.queue.clear()
            self._update_backpressure()
        return snap

    # -- disaggregated prefill/decode handoff (docs/ROBUSTNESS.md) -----
    def ready_handoffs(self) -> List:
        """Finished prefills parked for migration: ``(slot, req)`` for
        every slot in ``state="handoff"`` (``prefill_only`` replicas
        only — a mixed/decode replica never parks). The router harvests
        these each step and drives the KV migration."""
        return [(slot, r) for slot, r in enumerate(self.slots)
                if r is not None and r.state == "handoff"]

    def release_handoff(self, rid) -> bool:
        """Free the handoff slot for ``rid`` after the router has taken
        ownership (migrated the KV, or fallen back to a cold resume on
        the decode side): blocks back to the pool, slot reopened. The
        request is NOT retired here — its one terminal state lands on
        the destination replica. Returns False when ``rid`` holds no
        handoff slot (it timed out or was already released — the
        caller's snapshot path owns it then)."""
        for slot, r in enumerate(self.slots):
            if r is not None and r.rid == rid and r.state == "handoff":
                self._vacate(slot, r)
                return True
        return False

    # -- phases ----------------------------------------------------------
    def _expire(self, now: float) -> None:
        """Retire every request whose deadline has passed — slot holders
        free their blocks immediately (no zombie slot squatting), queued
        requests never claim one."""
        for slot in np.flatnonzero(now >= self._deadline).tolist():
            req = self.slots[slot]
            logger.warning(
                f"serving: request {req.rid} passed its deadline "
                f"({req.deadline}) with {len(req.out)} of "
                f"{req.max_new_tokens} tokens; timing out")
            self._finish(slot, req, now, state="timeout")
        if not self.queue:
            return
        keep = deque()
        for req in self.queue:
            if req.deadline is not None and now >= req.deadline:
                req.state = "timeout"
                req.finished_at = now
                # a migrated-in request expiring while queued must
                # return its parked landing, or the blocks leak
                self.cache.drop_parked(req.rid)
                self.finished.append(req)
                self._stat["timeouts"].inc()
                self.telemetry.tracer.event(
                    "finish", rid=req.rid, step=self._step_clock,
                    state="timeout", generated=len(req.out))
            else:
                keep.append(req)
        self.queue = keep

    def _unqueue(self, req: ServeRequest) -> None:
        """Remove ``req`` from the queue by IDENTITY (dataclass ``==``
        is unusable on array-carrying requests, and a parked request
        admitted out of line is not the head)."""
        for i, r in enumerate(self.queue):
            if r is req:
                del self.queue[i]
                return

    def _admit(self, now: float = 0.0) -> None:
        # FIFO head-of-line: no queue jumping, so a preempted-and-
        # requeued request (appendleft) resumes before newer arrivals
        while self.queue:
            free = np.flatnonzero(~self._held)
            if not free.size:
                break
            slot = int(free[0])
            req = self.queue[0]
            occupied = free.size < self.num_slots
            # idle engine: skip the watermark so a lone request that
            # fits the pool always makes progress (no livelock); the
            # admission charge covers only the uncached suffix when the
            # prefix cache can share blocks. Adapter-carrying requests
            # bypass prefix sharing entirely: the index keys blocks by
            # TOKENS only, but their K/V was computed under some
            # adapter's weights — a cross-tenant hit would serve
            # another adapter's activations (docs/ADAPTERS.md)
            tok_key = None if req.adapter_id is not None else req._work
            # migrated-in request (docs/ROBUSTNESS.md): the router
            # already landed its KV chain as a parked chain — adoption
            # needs no fresh blocks, so admission control is skipped
            parked = self.cache.has_parked(req.rid)
            if not parked:
                ok = self.cache.can_admit(len(req._work), tokens=tok_key,
                                          watermark=None if occupied
                                          else 0)
                if not ok:
                    # strict head-of-line would deadlock a disagg
                    # decode replica: the blocks a cold head request
                    # waits for can be HELD by parked migrated-in
                    # chains queued BEHIND it, and those only free by
                    # being served. Adoption consumes no fresh blocks,
                    # so a parked request may jump a blocked head —
                    # the one break from FIFO, taken only when FIFO
                    # cannot make progress (docs/ROBUSTNESS.md).
                    req = next((r for r in list(self.queue)[1:]
                                if self.cache.has_parked(r.rid)), None)
                    if req is None:
                        break
                    parked = True
                    tok_key = (None if req.adapter_id is not None
                               else req._work)
            cow0 = self.cache.cow_copies
            res0 = self.cache.host_restores
            try:
                if parked:
                    # the prompt's K/V is already resident: prefill
                    # covers only the emitted tail tokens (the same
                    # recompute window a prefix hit leaves), so decode
                    # resumes without re-prefilling the prompt
                    matched = self.cache.adopt_parked(slot, req.rid)
                    try:
                        # the migrated chain covers exactly the prompt;
                        # grow it to cover the emitted tail before the
                        # tail prefill writes there
                        self.cache.ensure_capacity(slot, len(req._work))
                    except CacheExhausted:
                        # cannot grow: degrade to a cold re-prefill —
                        # free the landing and retry the request as a
                        # normal admission (never a wrong token)
                        self.cache.free(slot)
                        break
                else:
                    matched = self.cache.allocate(slot, len(req._work),
                                                  tokens=tok_key)
            except CacheExhausted:
                # an injected (or racing) exhaustion at admission: the
                # request stays at the queue head and retries next step
                break
            if self.costs.enabled:
                # COW copies and host-tier restores the allocation
                # triggered are this request's bytes
                self.costs.charge_cow(req, self.cache.cow_copies - cow0)
                self.costs.charge_spill(self.cache.host_restores - res0,
                                        req=req, restore=True)
            arow = None
            if req.adapter_id is not None:
                try:
                    if self.adapters is None:
                        raise AdapterLoadError(
                            f"request {req.rid} names adapter "
                            f"{req.adapter_id!r} but lora_serve is off")
                    arow = self.adapters.acquire(req.adapter_id)
                except (AdapterLoadError, TransientDeviceError) as e:
                    # structured degradation (docs/ADAPTERS.md): the
                    # request retires with state="error" — the batch
                    # keeps serving, and a slot NEVER decodes with base
                    # (or stale) weights in place of its named adapter
                    self.cache.free(slot)
                    self._unqueue(req)
                    req.state = "error"
                    req.finished_at = now
                    self.finished.append(req)
                    self._stat["adapter_load_errors"].inc()
                    logger.warning(
                        f"serving: adapter {req.adapter_id!r} failed to "
                        f"load for request {req.rid} ({e}); retiring "
                        f"state=error")
                    self.telemetry.tracer.event(
                        "finish", rid=req.rid, step=self._step_clock,
                        state="error", generated=len(req.out))
                    continue
            self._unqueue(req)
            if arow is not None:
                self._slot_arows[slot] = arow
            # prefill resumes at the matched boundary — the shared
            # blocks' K/V is already resident, so those tokens are
            # never recomputed
            self._progress[slot] = matched
            if matched > 0 and not parked:
                self._stat["prefix_hits"].inc()
                self._stat["prefix_tokens_saved"].inc(matched)
            req._admit_seq = self._admit_counter
            self._admit_counter += 1
            # sampling lanes for this slot: resolved knobs become the
            # slot-indexed arrays the fused sampler reads; the seen mask
            # seeds from prompt+generated (req._work), so a
            # repetition-penalized request resumes with the identical
            # penalty state after eviction or drain
            params = sampling.resolve_params(req, self.temperature,
                                             self.top_k, self.seed)
            self._slot_params[slot] = params
            self.sampler.admit(slot, params, req._work)
            self._seat(slot, req, "prefill")
            self._accept_ewma[slot] = 1.0
            self._spec_obs[slot] = 0
            if self._h_temp is not None:
                self._h_temp.observe(params.temperature)
            self._stat["admitted"].inc()
            if self._h_qwait is not None and req.submitted_at is not None:
                self._h_qwait.observe(max(0.0, now - req.submitted_at),
                                      at=now)
            self.telemetry.tracer.event(
                "admit", rid=req.rid, step=self._step_clock, slot=slot,
                matched=int(matched), evictions=req.evictions)

    def _prefill_step(self, now: float) -> None:
        for slot in np.flatnonzero(self._prefilling).tolist():
            req = self.slots[slot]
            done = int(self._progress[slot])
            n = min(self.prefill_chunk, len(req._work) - done)
            attended = self.engine.prefill_attended(
                done, n, self.cache.block_size, self.cache.blocks_per_slot,
                self.cache.quantized)
            self._stat["prefill_attended_tokens_total"].inc(attended)
            self._stat["prefill_row_tokens_total"].inc(
                self.cache.tokens_per_slot)
            blocks = chunk_blocks(done, n, self.cache.block_size,
                                  self.cache.blocks_per_slot)[1]
            self._stat["prefill_write_blocks_total"].inc(blocks)
            self._mla_tiles.inc(self.engine.mla_prefill_tiles(
                done, self.cache.block_size))
            with self.telemetry.tracer.span(
                    "serve.prefill", rid=req.rid, step=self._step_clock,
                    slot=slot, start=done, n=n, history=done,
                    attended=attended, blocks=blocks,
                    tail=int(done > 0 and self.cache.cca_tail_bytes > 0),
                    state=int(done > 0
                              and self.cache.recurrent_state_bytes > 0),
                    ring_wrapped=int(self.cache.ring_wrapped(done + n))):
                if done == 0 and self._state_resets is not None:
                    self._state_resets.inc()
                    if req.evictions:
                        self._state_replays.inc()
                self._prefill_slot_chunk(slot, req, done, n, now)

    def _prefill_slot_chunk(self, slot: int, req: ServeRequest,
                            done: int, n: int, now: float) -> None:
        """One chunk of one slot's prompt: ``n`` tokens from ``done``."""
        tracer = self.telemetry.tracer
        chunk = np.zeros((self.prefill_chunk,), np.int32)
        chunk[:n] = req._work[done:done + n]
        # the slot's sampling lane rides every chunk (data, not a
        # signature change); only the FINAL chunk's sample is kept
        lane = self.sampler.lane(slot, len(req.out))
        lora = self._lora_args(slot)
        logits, tok, lp, *self.cache.pools = self._device_call(
            "serving.prefill",
            lambda *a: self.engine.prefill_into_slot(
                *a, scales=self.cache.scales, sample_state=lane, lora=lora),
            self.cache.k, self.cache.v, self.cache.tables[slot], chunk,
            done, n, now=now)
        self.cache.advance(slot, n)
        self._progress[slot] = done + n
        self._stat["prefill_chunks"].inc()
        # one prefill-chunk dispatch: n new tokens over `done`
        # cached context, whole cost owned by this slot's request
        self.costs.charge_prefill(req, n, done)
        tracer.event("prefill_chunk", rid=req.rid, step=self._step_clock,
                     slot=slot, start=done, n=n)
        if self._progress[slot] != len(req._work):
            return
        # prompt fully resident: publish its full blocks to the
        # prefix index (before _emit, which may free the slot)
        # so the NEXT request sharing this prefix skips them —
        # unless this slot decoded under an adapter: its K/V
        # carries that adapter's weights and must never be
        # served to another tenant (docs/ADAPTERS.md)
        if req.adapter_id is None:
            self.cache.register_prefix(slot, req._work)
        tracer.event("prefill_done", rid=req.rid, step=self._step_clock,
                     slot=slot)
        # final chunk: its last-position logits yielded the next
        # token inside the program (== generate()'s prefill
        # sample on the greedy lane; on resume, the recomputed
        # position is exactly the pre-eviction one, and the
        # sampled lane's key fold_in(key, len(out)) replays the
        # identical draw)
        with tracer.span("serve.pull", rid=req.rid, step=self._step_clock,
                         slot=slot, bytes=tok.nbytes + lp.nbytes, d2h=1):
            tok_h, lp_h = jax.device_get((tok, lp))  # dslint: disable=DS001 — final chunk only: ONE pull per prefill completion (the prefill-emitted token and its logprob, both copies started before either is awaited), not per-chunk work
        with tracer.span("serve.emit", rid=req.rid, step=self._step_clock,
                         slot=slot, tokens=1):
            self._emit_sampled(slot, req, int(tok_h[0]), float(lp_h[0]), now)
        if req.state not in TERMINAL_STATES:
            # prefill-only role: park the finished prefill for
            # the router's KV migration instead of decoding it
            self._seat(slot, req,
                       "handoff" if self.prefill_only else "decode")

    def _grow_decoding(self, now: float) -> int:
        """Room for ONE more token in every decoding slot; exhaustion
        evicts the youngest request rather than OOMing the pool. One
        comparison over the slots finds the few that need anything (a
        slot at a block border, once in ``block_size`` steps; a slot at
        its budget) and only those are visited, in slot order. While the
        fault injector has a fault armed every decoding slot is visited:
        ``cache.ensure`` is matched by its visit index, so chaos runs
        keep the cadence of one visit a decoding slot a step
        (docs/ROBUSTNESS.md). Returns the slots whose table grew."""
        cache = self.cache
        marked = self._decoding if self.faults.faults \
            else cache.needs_block(self._decoding)
        grown = 0
        for slot in np.flatnonzero(marked).tolist():
            if not self._decoding[slot]:
                continue            # evicted for an earlier slot's block
            req = self.slots[slot]
            if cache.at_capacity(slot):
                # block budget exhausted: the kernel's next cache write
                # would clamp into the slot's LAST LIVE block — finish
                # (truncate) the request before it reaches the kernel.
                # Eviction is no escape: the resume prompt is just as
                # long, so a preempted slot would requeue forever.
                logger.warning(
                    f"serving: request {req.rid} hit the per-slot block "
                    f"budget ({cache.tokens_per_slot} tokens) in "
                    f"slot {slot}; finishing with {len(req.out)} of "
                    f"{req.max_new_tokens} tokens")
                self._finish(slot, req, now)
                continue
            cow0 = cache.cow_copies
            owned0 = cache.owned_count[slot]
            while True:
                try:
                    cache.ensure_capacity(slot, int(cache.lengths[slot]) + 1)
                    break
                except CacheExhausted:
                    if self._evict_one(exclude=slot):
                        continue
                    # nobody else is evictable: preempt this very
                    # request — unless the storm guard has pinned it,
                    # in which case truncate rather than livelock
                    if req.evictions < self.max_evictions:
                        self._preempt(slot)
                    else:
                        self._stat["evict_capped"].inc()
                        logger.warning(
                            f"serving: request {req.rid} is eviction-"
                            f"pinned ({req.evictions} preemptions) and "
                            f"the pool cannot grow; finishing with "
                            f"{len(req.out)} of {req.max_new_tokens} "
                            f"tokens")
                        self._finish(slot, req, now)
                    break
            grown += int(cache.owned_count[slot] > owned0)
            if self.costs.enabled:
                # mid-decode divergence copies are this request's bytes
                self.costs.charge_cow(req, cache.cow_copies - cow0)
        return grown

    def _decode_step(self, now: float) -> int:
        self._grown = self._grow_decoding(now)
        live = np.flatnonzero(self._decoding)
        n_live = int(live.size)
        if self.telemetry.enabled:
            t = self._clock()
            cache = self.cache
            # cached rows the step reads in a layer that pages its whole
            # history: each live slot's tokens and the one it writes
            self._kv_tokens = int(cache.lengths[live].sum()) + n_live
            self._tiles = None
            if self._step_clock % self.telemetry.sample_every == 0:
                # on the sampled cadence (every slot's arithmetic; no
                # benchmark metric reads them): grid steps of a
                # paged_decode call (of the full table): the live slots'
                # run, beside `blocks` the fill of the tiles; the other
                # slots' (a trash-block tile of a slot with no request,
                # the progress of one in prefill) are the ones the plan
                # drops
                tiles = tiles_run(cache.lengths, cache.blocks_per_slot,
                                  cache.block_size,
                                  None if cache.ring_blocks
                                  else self.engine.cfg.attn_window,
                                  row_bytes=cache.tile_row_bytes)
                kv_steps = int(tiles[live].sum())
                self._tiles = (kv_steps, int(tiles.sum()) - kv_steps)
            self._self_counts += self._clock() - t
        if not n_live:
            return 0
        if self.spec_decode:
            occ = self._spec_decode_step(live.tolist(), now)
            if occ is not None:
                return occ
            # draft/verify faulted before dispatch: degrade THIS step to
            # the plain one-token path below (forward progress over
            # speed; the donated pools are intact, the live list is
            # unchanged — no slot was advanced or emitted into)
        elif self.decode_horizon > 1:
            occ = self._horizon_decode_step(live.tolist(), now)
            if occ is not None:
                return occ
            # horizon faulted before dispatch: degrade THIS step to the
            # plain single-step path below — same contract as spec
            # (pools intact, no slot state moved, never a dropped token)
        # the per-slot arrays are the operands (zeros where no slot
        # decodes); copies, so that what a wrapper of _device_call keeps
        # is this step's
        tokens = self._last_tok.copy()
        active = self._decoding.copy()
        gen_counts = self._gen.copy()
        lanes = self.sampler.lanes(gen_counts)
        budget = self.step_time_budget_s
        t0 = time.perf_counter() if budget is not None else 0.0
        lora = self._lora_args()
        logits, toks, lps, *self.cache.pools = self._device_call(
            "serving.decode",
            lambda *a: self.engine.decode_slots(
                *a, scales=self.cache.scales, sample_state=lanes, lora=lora),
            self.cache.k, self.cache.v, self.cache.tables,
            self.cache.lengths, tokens, active, self.decode_impl, now=now)
        if budget is not None:
            self._watchdog_note(time.perf_counter() - t0)
        self._stat["decode_steps"].inc()
        if self.costs.enabled:
            # one batched dispatch: each live slot decoded 1 token over
            # its own cached context; the weight read splits exactly
            self.costs.charge_batched("decode", live, 1,
                                      self.cache.lengths[live])
        # one host transfer covers every slot's token + logprob (the
        # sampler already ran inside the compiled decode program)
        tracer = self.telemetry.tracer
        with tracer.span("serve.pull", step=self._step_clock,
                         bytes=toks.nbytes + lps.nbytes, d2h=1):
            toks, lps = jax.device_get((toks, lps))
        with tracer.span("serve.emit", step=self._step_clock,
                         tokens=n_live) as s_emit:
            s_emit.set(slow=self._emit_step(live, toks, lps, now))
        return n_live

    def _emit_step(self, live: np.ndarray, toks: np.ndarray,
                   lps: np.ndarray, now: float) -> int:
        """Emit a plain decode step's tokens: ``toks[i]`` for every slot
        ``i`` of ``live``. The slots in the ``_slow`` mask (stop
        sequences, logprobs, a repetition penalty: _slow_emit) go through
        :meth:`_emit_sampled` one by one; for all the others the step is
        array operations and one bare loop of the two appends a request,
        and only the slots the terminal test marks are visited again.
        Requests finish in ascending slot order whichever way their slot
        emitted, so ``finished`` reads as if every slot had been walked.
        Returns how many slots emitted one by one."""
        self.cache.advance_each(live)
        fast_mask, fast, visit, n_slow = self._decoding, live, [], 0
        if self._slow.any():
            fast_mask = self._decoding & ~self._slow
            fast = np.flatnonzero(fast_mask)
            visit = np.flatnonzero(self._slow).tolist()
            n_slow = len(visit)
        if fast.size:
            got = toks[fast]
            self._last_tok[fast] = got
            self._gen[fast] += 1
            sampled = int(np.count_nonzero(self.sampler.temps[fast] > 0.0))
            if sampled:
                self._stat["sampled_tokens"].inc(sampled)
            slots, at = self.slots, fast.tolist()
            if self._h_tpot is not None:
                # telemetry on: a decoding request has its first token,
                # and _tok_t holds when each slot's last one was emitted
                t = self._clock()
                self._h_tpot.observe_many(
                    np.maximum(0.0, now - self._tok_t[fast]), at=now)
                self._tok_t[fast] = now
                self._self_hist += self._clock() - t
            for i, tok in zip(at, got.tolist()):
                req = slots[i]
                req.out.append(tok)
                req.token_times.append(now)
            # whole arrays under the mask (an empty slot reads 0 >= 0)
            ended = np.flatnonzero(
                fast_mask & ((self._gen >= self._max_new)
                             | (self._last_tok == self._eos)))
            if ended.size:
                visit = sorted(visit + ended.tolist())
        if n_slow:
            self._c_emit_slow.inc(n_slow)
        for i in visit:
            req = self.slots[i]
            if self._slow[i]:
                self._emit_sampled(
                    i, req, int(toks[i]),
                    float(lps[i]), now)  # dslint: disable=DS001 — lps is host numpy already (the single batched pull above)
            else:
                self._finish(i, req, now)
        return n_slow

    def _horizon_decode_step(self, live: List[int],
                             now: float) -> Optional[int]:
        """One fused multi-step decode over the decoding slots: up to
        ``decode_horizon`` iterations of the decode body in ONE compiled
        dispatch (engine.decode_horizon, docs/MULTISTEP.md), with each
        slot's emission budget and eos/stop predicates freezing finished
        lanes in-program. Admission, eviction, deadline and watchdog
        checks stay at this horizon boundary; the harvest replays each
        slot's produced tokens through the exact N=1 emission
        bookkeeping, so token streams — including mid-horizon stops and
        evict/requeue resumes — are bit-identical to single-step
        serving. Returns the occupancy, or None to degrade this step to
        the plain one-token path (an injected ``serving.horizon`` fault
        fires BEFORE any capacity or slot state moves — degraded
        horizons lose speed, never tokens).

        Capacity is opportunistic, mirroring the speculative path: the
        horizon wants N tokens of room, but a slot that cannot grow
        (pool pressure, per-slot budget) just runs a shorter horizon —
        eviction is never triggered FOR horizon tokens, only for the
        one committed token the plain preamble already guaranteed.
        Deadlined slots cap their budget at the worst-case token-tick
        overshoot, so no token is ever stamped past a deadline the N=1
        loop would have enforced."""
        N = self.decode_horizon
        try:
            self.faults.fire("serving.horizon")
        except TransientDeviceError:
            self._stat["horizon_fallbacks"].inc()
            logger.warning("serving: horizon fault; degrading this step "
                           "to single-step decode")
            return None
        tokens = np.zeros((self.num_slots,), np.int32)
        active = np.zeros((self.num_slots,), bool)
        gen_counts = np.zeros((self.num_slots,), np.int32)
        budgets = np.zeros((self.num_slots,), np.int32)
        eos_ids = np.full((self.num_slots,), -1, np.int32)
        stop_ids = np.zeros((self.num_slots, HORIZON_MAX_STOPS,
                             HORIZON_STOP_WIDTH), np.int32)
        stop_lens = np.zeros((self.num_slots, HORIZON_MAX_STOPS), np.int32)
        tail = np.full((self.num_slots, HORIZON_STOP_WIDTH), -1, np.int32)
        tick = self._token_tick
        for i in live:
            req = self.slots[i]
            tokens[i] = req.out[-1]
            active[i] = True
            gen_counts[i] = len(req.out)
            length = int(self.cache.lengths[i])
            granted = self.cache.horizon_budget(
                i, min(length + N, self.cache.tokens_per_slot))
            # b >= 1 always: the plain preamble secured one token of
            # room, an emitted-out request would already have finished,
            # and _expire retired anything past its deadline
            b = min(N, granted - length,
                    req.max_new_tokens - len(req.out))
            if req.deadline is not None and tick > 0.0:
                b = min(b, max(1, int(math.ceil(
                    (req.deadline - now) / tick))))
            budgets[i] = max(1, b)
            if req.eos_id is not None:
                eos_ids[i] = int(req.eos_id)
            if req.stop:
                row = 0
                for s in req.stop:
                    ls = len(s)
                    if 0 < ls <= HORIZON_STOP_WIDTH \
                            and row < HORIZON_MAX_STOPS:
                        stop_ids[i, row, HORIZON_STOP_WIDTH - ls:] = \
                            [int(t) for t in s]
                        stop_lens[i, row] = ls
                        row += 1
                w = min(len(req.out), HORIZON_STOP_WIDTH)
                if w:
                    tail[i, HORIZON_STOP_WIDTH - w:] = req.out[-w:]
        lanes = self.sampler.lanes(gen_counts)
        budget = self.step_time_budget_s
        t0 = time.perf_counter() if budget is not None else 0.0
        lora = self._lora_args()
        toks, lps, produced, done, *self.cache.pools = self._device_call(
            "serving.decode",
            lambda *a: self.engine.decode_horizon(
                *a, scales=self.cache.scales, sample_state=lanes, lora=lora),
            self.cache.k, self.cache.v, self.cache.tables,
            self.cache.lengths, tokens, active, N, budgets, eos_ids,
            stop_ids, stop_lens, tail, self.decode_impl, now=now)
        if budget is not None:
            self._watchdog_note(time.perf_counter() - t0,
                                scale=int(budgets[live].max()))
        self._stat["decode_steps"].inc()
        # ONE batched host transfer harvests the whole horizon: [N, B]
        # tokens + logprobs and the per-slot produced counts
        with self.telemetry.tracer.span(
                "serve.pull", step=self._step_clock, d2h=1,
                bytes=toks.nbytes + lps.nbytes + produced.nbytes):
            toks, lps, produced = jax.device_get((toks, lps, produced))
        if self.costs.enabled:
            # one fused dispatch: each live slot produced its own token
            # count over its own pre-advance context
            at = np.asarray(live)
            self.costs.charge_batched("decode", at, produced[at],
                                      self.cache.lengths[at])
        ticks = 1
        prod_by_slot = {}
        for i in live:
            req = self.slots[i]
            p = int(produced[i])
            prod_by_slot[i] = p
            # one advance covers the whole horizon (p <= the granted
            # capacity by the budget construction above); a mid-harvest
            # finish below frees the slot, releasing any surplus writes
            self.cache.advance(i, p)
            if self._h_horizon is not None:
                self._h_horizon.observe(p)
            for j in range(p):
                self._emit_sampled(
                    i, req, int(toks[j, i]), float(lps[j, i]),  # dslint: disable=DS001 — toks/lps are host numpy already (the single batched pull above)
                    now + j * tick)
                if req.state in TERMINAL_STATES:
                    # an unmodeled stop matched host-side before the
                    # budget ran out: the surplus in-program tokens die
                    # with the freed slot, streams stay exact
                    break
            ticks = max(ticks, p)
        self._horizon_ticks = ticks
        self.telemetry.tracer.event(
            "horizon_step", step=self._step_clock, n=N,
            produced=prod_by_slot)
        return len(live)

    def _spec_decode_step(self, live: List[int], now: float) -> Optional[int]:
        """One speculative iteration over the decoding slots: draft
        ``spec_k`` tokens per slot, verify all ``spec_k + 1`` positions
        in ONE program, accept each slot's draft prefix, emit accepted
        tokens plus the target's correction, roll the cache back past
        the first reject. A temperature=0 slot accepts by greedy-target
        agreement (bit-identical to spec-off greedy serving); a sampled
        slot runs per-position rejection sampling (Leviathan/Chen:
        accept the draft token x with prob min(1, p(x)/q(x)) — q is a
        point mass for the deterministic drafters, so that is p(x) —
        and resamples a rejection from the residual norm(max(0, p-q))),
        which is distribution-lossless against plain sampled decode
        (docs/SAMPLING.md). Returns the occupancy, or None to degrade
        this step to the plain one-token path (an injected draft/verify
        fault — both fire BEFORE dispatch, so no slot state has moved).

        Capacity is opportunistic: the chunk wants ``spec_k + 1`` tokens
        of room, but a slot that cannot grow (pool pressure, per-slot
        budget) just speculates shallower this step — eviction is never
        triggered FOR draft tokens, only for the one committed token the
        plain preamble already guaranteed. Adaptive depth rides the same
        cap: a slot whose acceptance EWMA fell under
        ``spec_accept_floor`` verifies only 1 draft token until its rate
        recovers (the chunk stays ``spec_k + 1`` wide — the static
        verify program never changes — the unverified suffix is simply
        rolled back like any rejection)."""
        G = self.spec_k + 1
        try:
            self.faults.fire("serving.spec_draft")
            proposals = {
                i: np.asarray(  # dslint: disable=DS001 — drafter output is host numpy (prompt-lookup never touches the device); this normalizes dtype/shape, no sync
                    self.draft.propose(self.slots[i].tokens, self.spec_k),
                    np.int32).ravel()
                for i in live}
        except TransientDeviceError:
            self._stat["spec_fallbacks"].inc()
            logger.warning("serving: draft fault; degrading this step "
                           "to plain decode")
            return None
        caps = {}
        for i in live:
            length = int(self.cache.lengths[i])
            want = min(length + G, self.cache.tokens_per_slot)
            if want > self.cache.capacity_tokens(i):
                cow0 = self.cache.cow_copies
                try:
                    self.cache.ensure_capacity(i, want)
                except CacheExhausted:
                    pass      # speculate into whatever room exists
                if self.costs.enabled:
                    self.costs.charge_cow(
                        self.slots[i], self.cache.cow_copies - cow0)
            caps[i] = min(self.cache.capacity_tokens(i),
                          self.cache.tokens_per_slot) - length
        tokens = np.zeros((self.num_slots, G), np.int32)
        active = np.zeros((self.num_slots,), bool)
        for i in live:
            tokens[i, 0] = self.slots[i].out[-1]   # the pending token
            tokens[i, 1:] = proposals[i][:self.spec_k]
            active[i] = True
        budget = self.step_time_budget_s
        t0 = time.perf_counter() if budget is not None else 0.0
        try:
            # no retry wrapper: a verify fault degrades to the plain
            # path (which retries) instead of re-speculating — the fault
            # fires before dispatch, so the donated pools are intact
            logits, *self.cache.pools = self.engine.verify_slots(
                self.cache.k, self.cache.v, self.cache.tables,
                self.cache.lengths, tokens, active, self.decode_impl,
                scales=self.cache.scales, lora=self._lora_args())
        except TransientDeviceError:
            self._stat["spec_fallbacks"].inc()
            logger.warning("serving: verify fault; degrading this step "
                           "to plain decode")
            return None
        if budget is not None:
            self._watchdog_note(time.perf_counter() - t0)
        self._stat["decode_steps"].inc()
        self._stat["spec_steps"].inc()
        if self.costs.enabled:
            # the verify program scores all G chunk positions per live
            # slot whatever gets accepted — the compute is spent either
            # way, so attribution bills the full chunk
            self.costs.charge_batched("verify", np.asarray(live), G,
                                      self.cache.lengths[live])
        # the target's greedy choice at every chunk position — the SAME
        # fp32-cast device argmax the fused sampler's greedy lane takes,
        # so accepted tokens are bit-identical to what plain decode
        # would have emitted
        greedy = np.asarray(jax.device_get(  # dslint: disable=DS001 — accept/reject is host control flow; one transfer per verify step replaces spec_k+1 plain-decode transfers
            jnp.argmax(logits.astype(jnp.float32), axis=-1)))
        # sampled slots (and greedy slots that want logprobs) need the
        # full verify logits host-side for the fp64 Leviathan math
        logits_host = None
        if any(self._slot_params[i] is not None
               and (self._slot_params[i].sampled or self.slots[i].logprobs)
               for i in live):
            logits_host = np.asarray(jax.device_get(  # dslint: disable=DS001 — fp64 accept/resample is host math by design; one transfer per verify step
                logits.astype(jnp.float32)))
        proposed = accepted = emitted = 0
        accept_by_slot = {}
        for i in live:
            req = self.slots[i]
            params = self._slot_params[i]
            # leading agreement, capped so lengths never outgrow the
            # blocks actually allocated (caps >= 1: the plain preamble
            # guaranteed room for the committed token)
            k_live = max(0, min(self.spec_k, caps[i] - 1))
            if (self.spec_accept_floor > 0.0 and k_live > 1
                    and self._spec_obs[i] >= self.spec_adapt_warmup
                    and self._accept_ewma[i] < self.spec_accept_floor):
                self._stat["spec_k_capped"].inc()
                k_live = 1
            prop = proposals[i]
            if params is not None and params.sampled:
                # rejection-sampling verify against the target's fp64
                # sampling distributions at each chunk position;
                # position j decides generation index len(out) + j, and
                # the uniforms are Philox(seed, index) — counter-based,
                # so a chunk boundary is invisible to the draw stream
                rows = sampling.fp64_dist(
                    logits_host[i, :k_live + 1], params.temperature,
                    top_k=params.top_k, top_p=params.top_p)
                toks, lps, acc = sampling.spec_verify_tokens(
                    rows, prop[:k_live], params.seed, len(req.out))
            else:
                acc = 0
                while acc < k_live and greedy[i, acc] == prop[acc]:
                    acc += 1
                toks = [int(t) for t in prop[:acc]] + [int(greedy[i, acc])]
                lps = [None] * len(toks)
                if req.logprobs:
                    # log p under plain softmax of the verify logits —
                    # the greedy lane's logprob source in sample_tokens
                    lps = [math.log(max(float(  # dslint: disable=DS001 — fp64 host math over logits_host (already pulled once above), no device sync
                        sampling.fp64_dist(logits_host[i, j], 1.0)[t]),
                        1e-300)) for j, t in enumerate(toks)]
            if k_live > 0:
                self._accept_ewma[i] = (0.8 * self._accept_ewma[i]
                                        + 0.2 * (acc / k_live))
                self._spec_obs[i] += 1
            proposed += k_live
            accepted += acc
            accept_by_slot[i] = acc
            # commit acc + 1 tokens (accepted drafts + the pending one
            # whose K/V this chunk wrote), then trim any tail block only
            # the rejected draft suffix was using
            new_len = int(self.cache.lengths[i]) + acc + 1
            self.cache.advance(i, acc + 1)
            self.cache.rollback(i, new_len)
            self._stat["spec_slot_steps"].inc()
            for tok, lp in zip(toks, lps):
                emitted += 1
                self._emit_sampled(i, req, int(tok), lp, now)
                if req.state in TERMINAL_STATES:
                    break      # max_new/eos truncation, same order as off
        self._stat["spec_proposed"].inc(proposed)
        self._stat["spec_accepted"].inc(accepted)
        self._stat["spec_emitted"].inc(emitted)
        if self._h_accept is not None:
            if proposed:
                self._h_accept.observe(accepted / proposed)
            self._h_tps.observe(emitted / len(live))
        self.telemetry.tracer.event(
            "spec_verify", step=self._step_clock, k=self.spec_k,
            accepted=accept_by_slot, emitted=emitted)
        return len(live)

    # -- helpers ---------------------------------------------------------
    def _spill_step(self) -> None:
        """Host-tier daemon tick: runs right AFTER the decode dispatch
        (the gather it queues overlaps the decode program; last tick's
        gather is harvested here, a full step after dispatch — the
        double buffer) and never on the admission path. Billed to the
        decode phase of ``serving_step_*_s`` (its own span is
        ``serve.spill``). The
        tick's host time answers to the step watchdog, but only an
        over-budget tick may strike — an in-budget tick must not reset
        the decode dispatch's own strikes."""
        if not self.host_tier:
            return
        t0 = time.perf_counter()
        sp0 = self.cache.host_spills
        self.cache.spill_tick()
        if self.costs.enabled:
            # refcount-zero spills have no owning request: the bytes
            # land in the accountant's system footprint
            self.costs.charge_spill(self.cache.host_spills - sp0)
        self._sync_host_stats()
        if self.step_time_budget_s is not None:
            elapsed = time.perf_counter() - t0
            if elapsed > self.step_time_budget_s:
                self._watchdog_note(elapsed)

    def _sync_host_stats(self) -> None:
        """Mirror the cache's host-tier counters into the serving stats
        (single source of truth stays in the cache) and feed the
        restore-latency histogram from the samples the cache buffered
        since the last tick."""
        c = self.cache
        self._stat["host_blocks"].set(c.host_blocks)
        self._stat["host_bytes"].set(c.host_bytes)
        self._stat["host_spills"].set(c.host_spills)
        self._stat["host_restores"].set(c.host_restores)
        self._stat["host_restore_failures"].set(c.host_restore_failures)
        samples = c.drain_restore_ms()
        if self._g_host_bytes is not None:
            self._g_host_bytes.set(c.host_bytes)
        if self._h_host_restore is not None:
            for ms in samples:
                self._h_host_restore.observe(ms)

    def _watchdog_note(self, elapsed: float, scale: int = 1) -> None:
        """Score one decode/verify dispatch against the step budget:
        consecutive over-budget dispatches accumulate strikes until the
        grace runs out, then ``step()`` raises DegradedError AFTER this
        step's bookkeeping (nothing lost or double-counted on resume).
        ``scale`` stretches the budget for dispatches that legitimately
        do more than one step of work — a fused horizon doing up to N
        decode iterations answers to N single-step budgets, not one."""
        budget = self.step_time_budget_s * max(1, int(scale))
        if elapsed > budget:
            self._over_budget += 1
            self._stat["watchdog_trips"].inc()
            self.telemetry.tracer.event(
                "watchdog", step=self._step_clock,
                elapsed_s=round(elapsed, 6),
                strikes=self._over_budget)
            if self._over_budget >= self.watchdog_grace:
                self._watchdog_msg = (
                    f"decode step over budget "
                    f"({elapsed * 1e3:.1f}ms > "
                    f"{budget * 1e3:.1f}ms) {self._over_budget} "
                    f"consecutive times — degraded")
        else:
            self._over_budget = 0
    def _deadline_slack(self, now: Optional[float]) -> Optional[float]:
        """Tightest remaining deadline margin among active slots (the
        requests a retry sleep would stall), or None when no slot
        carries a deadline. Clamped at 0 — an already-expired request
        must not turn the cap negative."""
        if now is None:
            return None
        soonest = float(self._deadline.min())
        return None if soonest == math.inf else max(0.0, soonest - now)

    def _device_call(self, site: str, fn, *args, now: Optional[float] = None):
        """Run a slot program with fault injection + transient-error
        retry. Faults (and any real pre-dispatch failure) fire BEFORE
        ``fn`` touches the donated pools, so a retry re-dispatches
        against intact buffers; backoff doubles per attempt with
        deterministic jitter from the injector's seeded rng. Each sleep
        is capped at the tightest remaining deadline among active slots
        (``now`` is the scheduler-clock step stamp): a backoff can
        never sleep a live request past its deadline — with no margin
        left, retries spin immediately and expiry decides at the next
        step. ``args`` lead with the K and V pools and the program's host
        operands in the wrapper's order: the benchmark wraps this method
        and reads them by position (``benchmark/harness/spans.py``,
        ``benchmark/drivers/serve.py`` ``on_dispatch``)."""
        delay = self.retry_backoff_s
        attempt = 0
        tracer = self.telemetry.tracer
        while True:
            try:
                with tracer.span("serve.dispatch", step=self._step_clock,
                                 site=site, attempt=attempt,
                                 prev=self._gap_prev_site,
                                 after_empty=int(self._gap_empty)) \
                        as dispatch:
                    with tracer.span("serve.dispatch.enqueue") as enqueue:
                        # host: arguments, the one transfer, launch
                        self.faults.fire(site)
                        out = fn(*args)
                        h2d, h2d_bytes = self.engine.h2d
                        enqueue.set(h2d=h2d, h2d_bytes=h2d_bytes)
                    # dispatch is async and every caller harvests the
                    # result at once: the block makes the watchdog's
                    # elapsed time cover the execution, not the enqueue
                    with tracer.span("serve.dispatch.wait") as wait:
                        out = jax.block_until_ready(out)
                    if self._h_step is not None:
                        self._account_gap(site, dispatch, enqueue, wait)
                return out
            except TransientDeviceError:
                if attempt >= self.max_retries:
                    raise
                attempt += 1
                self._stat["retries"].inc()
                pause = min(delay + self.faults.jitter(delay * 0.5), 0.5)
                slack = self._deadline_slack(now)
                if slack is not None:
                    pause = min(pause, slack)
                logger.warning(
                    f"serving: transient device error at {site} "
                    f"(attempt {attempt}/{self.max_retries}); retrying "
                    f"in {pause * 1e3:.1f}ms")
                time.sleep(pause)
                delay *= 2

    def _account_gap(self, site: str, dispatch, enqueue, wait) -> None:
        """The gap before a dispatch that went through: the host seconds
        in which no program was enqueued, from the return of the previous
        dispatch's ``serve.dispatch.wait`` to the return of this one's
        ``serve.dispatch.enqueue``, on the stamps those spans and
        ``serve.step`` took. Its parts: ``enqueue`` (this enqueue span),
        ``caller`` (what lay outside every ``serve.step``: the loop that
        calls ``step``) and ``sched`` (the rest: a retried attempt and
        its backoff with it). A gap after an empty engine is a pause:
        it goes to ``serving_engine_empty_seconds_total`` alone.

        ``self_us`` beside ``gap_us`` is the part of THIS gap that the
        telemetry plane spent on its own account, which an engine with
        telemetry off would not have: the sum of what was stamped since
        the previous dispatch's wait returned, on the same clock, by
        part (``self_parts``, microseconds, in the order of
        telemetry/tracer.py ``SELF_PARTS``):
        ``spans`` (inside ``_Span.__enter__`` before ``t0`` and inside
        ``__exit__`` after ``t1``), ``accountant`` (the cost charges and
        folds), ``histograms`` (the step's observes, and this method),
        ``counts`` (``_span_counts``, the decode step's ``kv_tokens`` and
        sampled tiles), ``gauges`` (``_sample_gauges`` on its cadence)
        and ``hidden``: the enqueue span's exit and the wait span's
        entry, which ran under this dispatch's program and lie in no
        gap, so ``self_us`` leaves them out and ``self_us <= gap_us``.
        Not stamped, so in neither: the keyword arguments a ``span(...)``
        call evaluates, the ``if telemetry`` tests, the per-token path's
        observes (docs/OBSERVABILITY.md "Overhead" has their size)."""
        tracer = self.telemetry.tracer
        t = self._clock()
        prev_t, self._gap_prev_t = self._gap_prev_t, wait.t1
        caller, self._gap_caller = self._gap_caller, 0.0
        empty, self._gap_empty = self._gap_empty, False
        self._gap_prev_site = site
        self._c_wait.inc(wait.dur)
        # the wait span's exit came after wait.t1: it is the next gap's
        hidden = enqueue.o1 + wait.o0
        spans = tracer.span_self - hidden - wait.o1
        acct, hist = self.costs.self_s, self._self_hist
        counts, gauges = self._self_counts, self._self_gauges
        tracer.span_self, self.costs.self_s = wait.o1, 0.0
        self._self_hist = self._self_counts = self._self_gauges = 0.0
        own = spans + acct + hist + counts + gauges
        self._c_self.inc(own + hidden)
        if prev_t is not None:
            gap = enqueue.t1 - prev_t
            gap_us = round(gap * 1e6)
            dispatch.set(
                gap_us=gap_us, caller_us=round(caller * 1e6),
                # the parts lie inside the gap; min() is for the rounding
                self_us=min(round(own * 1e6), gap_us),
                self_parts=(spans * 1e6, acct * 1e6, hist * 1e6,
                            counts * 1e6, gauges * 1e6, hidden * 1e6))
            if empty:
                self._c_empty.inc(gap)
            else:
                self._h_gap.observe(gap)
                self._c_gap_enqueue.inc(enqueue.dur)
                self._c_gap_caller.inc(caller)
                self._c_gap_sched.inc(gap - enqueue.dur - caller)
        self._self_hist += self._clock() - t

    def read_expert_counters(self) -> Dict[str, Dict[str, float]]:
        """Pull the expert-share layers' counters from the device (they
        accumulate there, one add per dispatch, and cost no transfer
        until read) into the registry's ``moe_*`` gauges. Returns
        ``{"prefill": {...}, "decode": {...}}`` by moe/expert_share
        ``stat_fields``, or {} when the model has no such layers or telemetry
        is off."""
        stats = getattr(self.cache.k, "stats", None)
        if stats is None:
            return {}
        from deepspeed_tpu.moe.expert_share import stat_fields
        rows = np.asarray(jax.device_get(stats), np.int64)  # dslint: disable=DS001 — pulled on demand, never per dispatch
        out = {}
        for phase, row in zip(("prefill", "decode"), rows):
            vals = dict(zip(stat_fields(self.engine.cfg),
                            (int(v) for v in row)))
            out[phase] = vals
            calls = max(vals["layer_calls"], 1)
            for name, value in (
                    ("pairs_held", vals["pairs_held"]),
                    ("pairs_total", vals["pairs_total"]),
                    ("busiest_expert_pairs_mean",
                     vals["busiest_expert_pairs"] / calls),
                    ("expert_pairs_mean", vals["pairs_held"] / calls
                     / max(self.engine.cfg.held[1], 1)),
                    ("experts_touched_mean",
                     vals["experts_touched"] / calls),
                    # only ReLU-gated experts count these (in sixteens)
                    ("act_zero", vals.get("act_zero")),
                    ("act_total", vals.get("act_total")),
                    # only a router with a skip output counts these
                    ("pairs_skipped", vals.get("pairs_skipped")),
                    # only a router with zero-compute experts these
                    ("pairs_zero", vals.get("pairs_zero")),
                    ("real_pairs_max_token_mean",
                     vals["real_pairs_max_token"] / calls
                     if "real_pairs_max_token" in vals else None),
                    # only a shared expert with a gate of its own: 256ths
                    # in sixteens, over the tokens (pairs_total / k)
                    ("shared_gate_mean",
                     vals["shared_gate_q8"] / 16.0 * self.engine.cfg.moe_k
                     / max(vals["pairs_total"], 1)
                     if "shared_gate_q8" in vals else None)):
                if value is None:
                    continue
                self.metrics.gauge(
                    f"moe_{phase}_{name}",
                    f"expert-share layers, {phase} dispatches: {name} "
                    f"(moe/expert_share.py)").set(float(value))
        return out

    def _update_backpressure(self) -> None:
        if self.max_queue:
            self._stat["backpressure"].set(round(
                len(self.queue) / self.max_queue, 4))
        else:
            self._stat["backpressure"].set(0.0)

    def _sample_gauges(self) -> None:
        """Sampled-step gauge refresh: HBM block states + prefix hit
        rate. Host numpy reductions — cheap, but they run every
        ``telemetry.sample_every``-th step, not every step."""
        t = self._clock()
        self._g_held.set(int(self.cache.held_blocks))
        self._g_cached.set(int(self.cache.cached_blocks))
        self._g_free.set(int(self.cache.free_blocks))
        self._g_ring_used.set(self.cache.ring_rows_used)
        admitted = self._stat["admitted"].value
        self._g_hit_rate.set(
            round(self._stat["prefix_hits"].value / admitted, 4)
            if admitted else 0.0)
        if self._h_kv_err is not None:
            # half the hottest block's quantization step — an upper
            # bound on the elementwise |dequant - original| error; one
            # device_get, riding the sampled cadence only
            step = jax.device_get(jnp.maximum(  # dslint: disable=DS001 — sampled-cadence pull, mirrors the gauge refresh above
                jnp.max(self.cache.k_scale), jnp.max(self.cache.v_scale)))
            self._h_kv_err.observe(float(step) / 2.0)
        self._self_gauges += self._clock() - t

    def _degraded(self, message: str) -> DegradedError:
        # the flight recorder fires BEFORE the error leaves the engine:
        # whatever the caller does with the exception, the postmortem
        # artifact is already on disk (noop twin when the recorder is
        # off — one attribute access on this already-cold path)
        self.flight.dump(f"degraded: {message}")
        return DegradedError(
            message,
            results={r.rid: r.tokens for r in self.finished},
            finished=list(self.finished),
            pending=self.pending_snapshot(),
            stats=dict(self.stats))

    def capture_profile(self, steps: int, outdir: str,
                        now: Optional[float] = None) -> str:
        """On-demand ``jax.profiler`` capture window: trace exactly
        ``steps`` scheduler iterations (each a horizon boundary — the
        capture never straddles a partial fused dispatch) into
        ``outdir`` (TensorBoard/XProf layout; ``tools/trace_analyze.py
        read <outdir>`` summarizes it). Returns ``outdir``."""
        jax.profiler.start_trace(outdir)
        try:
            for _ in range(max(1, int(steps))):
                if not self.busy:
                    break
                self.step(now)
        finally:
            jax.profiler.stop_trace()
        return outdir

    def _slow_emit(self, slot: int, req: ServeRequest) -> bool:
        """Whether a decoding slot's tokens go through
        :meth:`_emit_sampled` one by one: its request matches stop
        sequences against ``out``, records log-probabilities, or marks
        the sampler's ``seen`` mask (a repetition penalty). Read from the
        request and the slot's sampling lane where the request starts to
        decode; nothing else is per token for a request without them."""
        return bool(req.stop or req.logprobs
                    or self.sampler.rep_pens[slot] != 1.0)

    def _seat(self, slot: int, req: ServeRequest, state: str) -> None:
        """``req`` holds ``slot`` in ``state`` (prefill | decode |
        handoff). With :meth:`_vacate` the ONE pair that writes
        ``slots[slot]`` and a slot holder's ``state``, because it also
        writes the per-slot arrays a step reads instead of the requests:
        the arrays cannot disagree with the requests."""
        self.slots[slot] = req
        req.state = state
        self._held[slot] = True
        self._deadline[slot] = np.inf if req.deadline is None \
            else req.deadline
        self._eos[slot] = -1 if req.eos_id is None else req.eos_id
        self._max_new[slot] = req.max_new_tokens
        decoding = state == "decode"
        self._prefilling[slot] = state == "prefill"
        self._decoding[slot] = decoding
        self._slow[slot] = decoding and self._slow_emit(slot, req)
        self._last_tok[slot] = req.out[-1] if decoding else 0
        self._gen[slot] = len(req.out) if decoding else 0
        if decoding and self._tok_t is not None and req.token_times:
            self._tok_t[slot] = req.token_times[-1]

    def _vacate(self, slot: int, req: ServeRequest) -> None:
        """``req`` gives ``slot`` up (finished, preempted, handed off or
        drained): its adapter pin, blocks and sampling lane go back and
        the slot's arrays read as an empty slot's (with cost accounting
        on, what the slot accrued is folded onto ``req`` first). The
        caller has set the state ``req`` leaves in."""
        if self.costs.enabled:
            self.costs.fold(slot, req)
        self._release_adapter(slot, req)
        self.cache.free(slot)
        self.slots[slot] = None
        self.sampler.release(slot)
        self._slot_params[slot] = None
        self._held[slot] = self._prefilling[slot] = False
        self._decoding[slot] = self._slow[slot] = False
        self._deadline[slot] = np.inf
        self._last_tok[slot] = self._gen[slot] = 0

    def _release_adapter(self, slot: int, req: ServeRequest) -> None:
        """Drop the slot's adapter pin (if it holds one) and zero its
        table row. The nonzero row IS the pin marker — a request whose
        acquire failed never set it, so release stays balanced."""
        if self.adapters is None or req.adapter_id is None:
            return
        if not self._slot_arows[slot].any():
            return
        self.adapters.release(req.adapter_id)
        self._slot_arows[slot] = 0

    def _finish(self, slot: int, req: ServeRequest, now: float,
                state: str = "done") -> None:
        """Retire a request: blocks back to the pool, slot reopened."""
        req.state = state
        req.finished_at = now
        self._vacate(slot, req)
        self.finished.append(req)
        if state == "timeout":
            self._stat["timeouts"].inc()
        else:
            self._stat["completed"].inc()
        self.telemetry.tracer.event(
            "finish", rid=req.rid, step=self._step_clock, slot=slot,
            state=state, generated=len(req.out))

    def _emit_sampled(self, slot: int, req: ServeRequest, tok: int,
                      lp: Optional[float], now: float) -> None:
        """Emit one token the fused sampler (or the spec verify)
        already chose: record its logprob, feed the repetition-penalty
        seen mask, count sampled lanes, then run the shared terminal-
        state bookkeeping."""
        self.sampler.observe(slot, tok)
        if req.logprobs and lp is not None:
            req.out_logprobs.append(float(lp))
        if self.sampler.temps[slot] > 0.0:
            self._stat["sampled_tokens"].inc()
        self._emit_token(slot, req, tok, now)

    def _emit_token(self, slot: int, req: ServeRequest, tok: int,
                    now: float) -> None:
        """Record one emitted token: output list, latency stamps,
        TTFT/TPOT histograms, terminal-state check (stop sequence,
        max_new, eos). Tokens arrive already chosen — by the fused
        in-program sampler or by the speculative verify."""
        prev = req.token_times[-1] if req.token_times else None
        req.out.append(tok)
        req.token_times.append(now)
        if self._decoding[slot]:
            # the next step's operands (a prefill's first token: _seat)
            self._last_tok[slot] = tok
            self._gen[slot] = len(req.out)
        if req.first_token_at is None:
            req.first_token_at = now
            if self._h_ttft is not None and req.submitted_at is not None:
                self._h_ttft.observe(max(0.0, now - req.submitted_at),
                                     at=now)
            self.telemetry.tracer.event(
                "first_token", rid=req.rid, step=self._step_clock, slot=slot)
        elif self._h_tpot is not None and prev is not None:
            self._h_tpot.observe(max(0.0, now - prev), at=now)
            self._tok_t[slot] = now
        if req.stop:
            for s in req.stop:
                ls = len(s)
                if ls and len(req.out) >= ls \
                        and req.out[-ls:] == [int(t) for t in s]:
                    # matched stop tokens stay IN out: the resume/drain
                    # contract replays the true emitted stream
                    self._stat["stop_hits"].inc()
                    self._finish(slot, req, now)
                    return
        if (len(req.out) >= req.max_new_tokens
                or (req.eos_id is not None and tok == req.eos_id)):
            self._finish(slot, req, now)

    def _evict_one(self, exclude: int) -> bool:
        """Preempt the most recently admitted live request (LIFO — the
        oldest work is closest to done) other than ``exclude``, skipping
        requests at the eviction cap: a pinned request cannot be chosen
        again, so the oldest victim of a storm is guaranteed forward
        progress."""
        victim = None
        capped = 0
        for i, r in enumerate(self.slots):
            if i == exclude or r is None:
                continue
            if r.evictions >= self.max_evictions:
                capped += 1
                continue
            if victim is None or r._admit_seq > self.slots[victim]._admit_seq:
                victim = i
        if victim is None:
            if capped:
                self._stat["evict_capped"].inc(capped)
            return False
        self._preempt(victim)
        return True

    def _preempt(self, slot: int) -> None:
        """Free the slot and requeue its request for recompute-on-resume:
        the new working prompt is prompt+generated, whose re-prefill
        reproduces the pre-eviction cache and next-token logits exactly.
        That holds for state no block holds too (a window ring, a
        convolution tail, a recurrent layer's state): the
        replay starts at position 0, where a chunk reads nothing of what
        the slot held, and rebuilds it."""
        req = self.slots[slot]
        logger.info(f"serving: evicting request {req.rid} from slot {slot} "
                    f"({self.cache.free_blocks} blocks free)")
        req._work = req.tokens
        req.state = "queued"
        req.evictions += 1
        self._stat["evictions"].inc()
        self.telemetry.tracer.event(
            "evict", rid=req.rid, step=self._step_clock, slot=slot,
            generated=len(req.out))
        self._vacate(slot, req)
        self.queue.appendleft(req)
