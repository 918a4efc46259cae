"""Loopback validation of the offload step's 3-stage overlap — runs on
the CPU, no TPU needed.

The offload ratio on a real host link has not been measured on the
current machine (PERF.md); the ~1.3-1.4x claim for a PCIe link was
computed, never enforced. This tool closes that gap by emulating a
PCIe-class link around the REAL ``HostOffloadOptimizer.step`` schedule
(no reimplementation):

- stage-1 ``d2h_enqueue`` probes timestamp each transfer's launch and
  assign it a FIFO ordinal (a DMA queue serializes);
- the stage-2 materialization seam (``_read_shard``) blocks until
  ``t0 + (ordinal+1) * bytes/BW`` — the completion semantics of an
  async DMA behind a serialized link;
- the measured wall time is compared against the ideal two-stage
  pipeline bound (simulated with the bare run's per-shard Adam times)
  and the no-overlap serial model.

Prints one JSON line per link speed:
  efficiency   = T_ideal_pipeline / T_measured  (1.0 = perfect overlap)
  vs_serial    = T_measured / T_serial_model    (<1.0 = overlap wins)
Reference budget: overlapped offload step <= 1.5x the fused step
(ref: runtime/swap_tensor/pipelined_optimizer_swapper.py:60).

Usage: python tools/offload_loopback.py [bw_gbps ...]   (default 1 4)
"""

import json
import sys
import time

sys.path.insert(0, ".")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from deepspeed_tpu.runtime.zero import offload as off  # noqa: E402


def build(n_leaves: int, elems: int):
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))
    shard = NamedSharding(mesh, P(None))
    rng = np.random.default_rng(0)
    params = {f"w{i}": rng.standard_normal(elems).astype(np.float32)
              for i in range(n_leaves)}
    shardings = {k: shard for k in params}
    opt = off.HostOffloadOptimizer(params, lr_schedule=lambda s: 1e-3,
                                   shardings=shardings)
    grads = {k: jax.device_put(
        rng.standard_normal(elems).astype(np.float32), shard)
        for k in params}
    return opt, grads


class WallClock:
    """The host's own clock: the CLI's."""
    now = staticmethod(time.perf_counter)

    def wait(self, until: float):
        time.sleep(max(0.0, until - self.now()))

    def adam_done(self):
        pass


class ModelledClock:
    """A clock that is MODELLED, not read: it advances by the link's waits
    and by ``adam_s`` at every ``adam_done``, so what :func:`run` returns
    follows from the ORDER in which the real step enqueues, waits and
    updates, whatever the host's load (tests/test_offload.py)."""

    def __init__(self, adam_s: float):
        self.t, self.adam_s = 0.0, adam_s

    def now(self) -> float:
        return self.t

    def wait(self, until: float):
        self.t = max(self.t, until)

    def adam_done(self):
        self.t += self.adam_s


def timed_step(opt, grads, clock, read_seam=None, on_event=None):
    """One step with every pipeline event of the main thread stamped on
    ``clock``; returns (wall, events)."""
    import threading
    main = threading.main_thread()
    events = []

    def probe(ev, i, k):
        # main-thread filter: when run inside the test suite, a prior
        # engine's DPU background thread may still fire the global probe
        if threading.current_thread() is not main:
            return
        if ev == "adam_done":
            clock.adam_done()
        events.append((ev, i, k, clock.now()))
        if on_event is not None:
            on_event(ev, i, k)

    off._pipeline_probe = probe
    off._read_shard = read_seam
    try:
        t0 = clock.now()
        opt.step(grads)
        wall = clock.now() - t0
    finally:
        off._pipeline_probe = None
        off._read_shard = None
    return wall, events


def adam_durations(events):
    """Per-shard Adam time from consecutive adam_done stamps in a bare
    (no-link) run — stage 2 is back-to-back there, so gaps ~= durations."""
    stamps = [t for ev, _, _, t in events if ev == "adam_done"]
    d2h_end = max(t for ev, _, _, t in events if ev == "d2h_enqueue")
    durs = [stamps[0] - d2h_end]
    durs += [b - a for a, b in zip(stamps, stamps[1:])]
    return durs


def ideal_pipeline(t_x: float, adam: list) -> float:
    """Two-stage FIFO pipeline bound: transfer k completes at (k+1)*t_x,
    Adam k starts at max(avail_k, adam_end_{k-1}); +t_x tail for the last
    h2d riding the same link."""
    end = 0.0
    for k, a in enumerate(adam):
        end = max((k + 1) * t_x, end) + a
    return end + t_x


def run(bw_gbps: float, n_leaves: int = 10, elems: int = 8_000_000,
        clock=None):
    """One step behind an emulated link of ``bw_gbps`` on ``clock`` (the
    host's by default); returns (efficiency, vs_serial)."""
    clock = clock or WallClock()
    opt, grads = build(n_leaves, elems)
    opt.step(grads)                      # warmup: optimizer state init
    bare_wall, bare_ev = timed_step(opt, grads, clock)
    adam = adam_durations(bare_ev)

    bytes_per = elems * 4
    t_x = bytes_per / (bw_gbps * 1e9)

    enq = {}

    def read_seam(i, k, raw):
        # FIFO-serialized DMA completion: ordinal assigned at enqueue.
        # Unknown keys (a foreign engine's background step) pass through.
        tgt = enq.get((i, k))
        if tgt is not None:
            clock.wait(tgt)
        return raw

    t0_holder = {}

    def on_enqueue(ev, i, k):
        # re-timestamp enqueues with FIFO ordinals inside the probe
        if ev == "d2h_enqueue":
            t0 = t0_holder.setdefault("t0", clock.now())
            enq[(i, k)] = t0 + (len(enq) + 1) * t_x

    wall, _ = timed_step(opt, grads, clock, read_seam, on_enqueue)

    ideal = ideal_pipeline(t_x, adam)
    serial = n_leaves * t_x + sum(adam) + t_x    # no-overlap model
    print(json.dumps({
        "metric": "offload_pipeline_efficiency",
        "link_gbps": bw_gbps,
        "n_shards": n_leaves,
        "shard_mb": round(bytes_per / 1e6, 1),
        "t_transfer_ms": round(t_x * 1e3, 1),
        "t_adam_total_ms": round(sum(adam) * 1e3, 1),
        "measured_ms": round(wall * 1e3, 1),
        "ideal_pipeline_ms": round(ideal * 1e3, 1),
        "serial_model_ms": round(serial * 1e3, 1),
        "efficiency": round(ideal / wall, 3),
        "vs_serial": round(wall / serial, 3),
        "bare_step_ms": round(bare_wall * 1e3, 1),
    }), flush=True)
    return ideal / wall, wall / serial


def main():
    speeds = [float(a) for a in sys.argv[1:]] or [1.0, 4.0]
    for bw in speeds:
        run(bw)


if __name__ == "__main__":
    main()
