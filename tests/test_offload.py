"""ZeRO-Offload / ZeRO-Infinity tier tests.

Mirrors the reference's offload coverage (ref: tests/unit/test_zero.py
cpu_offload configs, tests/unit/test_aio.py swap paths): host Adam step
parity with the fused device path, swapper roundtrips, and engine training
convergence with cpu/nvme offload.
"""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.runtime.swap_tensor import (OptimizerStateSwapper,
                                               PipelinedOptimizerSwapper,
                                               AsyncTensorSwapper)
from deepspeed_tpu.ops.aio import AsyncIOHandle
from tests.simple_model import (random_batch, simple_model_loss,
                                simple_model_params)

HIDDEN = 32


def test_optimizer_swapper_roundtrip(tmp_path):
    sw = OptimizerStateSwapper(str(tmp_path), n_tensors=2)
    rng = np.random.default_rng(0)
    m = rng.standard_normal(10_000).astype(np.float32)
    v = rng.standard_normal(10_000).astype(np.float32)
    sw.swap_out("layer0", [m, v])
    assert sw.has_state("layer0")
    m2, v2 = sw.swap_in("layer0")
    np.testing.assert_array_equal(m, m2)
    np.testing.assert_array_equal(v, v2)
    sw.purge()
    assert not sw.has_state("layer0")


def test_pipelined_swapper_prefetch(tmp_path):
    sw = PipelinedOptimizerSwapper(str(tmp_path), n_tensors=2)
    rng = np.random.default_rng(1)
    tensors = {}
    for i in range(4):
        m = rng.standard_normal(5000).astype(np.float32)
        v = rng.standard_normal(5000).astype(np.float32)
        tensors[str(i)] = (m, v)
        sw.swap_out(str(i), [m, v])
    # pipelined loop: prefetch i+1 while "computing" on i
    for i in range(4):
        if i + 1 < 4:
            sw.prefetch(str(i + 1))
        m, v = sw.swap_in(str(i))
        np.testing.assert_array_equal(m, tensors[str(i)][0])
        np.testing.assert_array_equal(v, tensors[str(i)][1])
        sw.swap_out_async(str(i), [m * 2, v * 2])
    sw.finish()
    m, v = sw.swap_in("2")
    np.testing.assert_array_equal(m, tensors["2"][0] * 2)


def test_async_tensor_swapper(tmp_path):
    aio = AsyncIOHandle()
    sw = AsyncTensorSwapper(aio, buffer_count=2, buffer_size=1 << 16)
    rng = np.random.default_rng(2)
    arrays = [rng.standard_normal(3000).astype(np.float32) for _ in range(5)]
    for i, a in enumerate(arrays):
        sw.swap_out(a, str(tmp_path / f"a{i}.swp"))
    sw.wait()
    for i, a in enumerate(arrays):
        out = np.empty_like(a)
        aio.sync_pread(out, str(tmp_path / f"a{i}.swp"))
        np.testing.assert_array_equal(a, out)
    aio.close()


def _train(config, steps=20, seed=0):
    params = simple_model_params(hidden_dim=HIDDEN, nlayers=2, seed=seed)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=simple_model_loss, model_parameters=params, config=config)
    losses = []
    for i in range(steps):
        batch = random_batch(config["train_batch_size"], HIDDEN, seed=i % 4)
        m = engine.train_batch(batch)
        losses.append(float(m["loss"]))
    return engine, losses


def _base_config(**zero_extra):
    return {
        "train_batch_size": 8,
        "bf16": {"enabled": True},
        "optimizer": {"type": "adamw",
                      "params": {"lr": 1e-2, "weight_decay": 0.01}},
        "zero_optimization": {"stage": 1, **zero_extra},
        "steps_per_print": 1000,
    }


def test_cpu_offload_trains():
    cfg = _base_config(offload_optimizer={"device": "cpu"})
    engine, losses = _train(cfg, steps=25)
    assert engine.offload_enabled
    assert losses[-1] < losses[0] * 0.5, losses


def test_cpu_offload_matches_fused_path():
    """Offloaded host-Adam trajectory tracks the fused device path
    (both bf16 compute; tolerances cover bf16 param rounding)."""
    cfg_off = _base_config(offload_optimizer={"device": "cpu"})
    _, losses_off = _train(cfg_off, steps=10)
    cfg_dev = _base_config()
    _, losses_dev = _train(cfg_dev, steps=10)
    np.testing.assert_allclose(losses_off, losses_dev, rtol=0.25, atol=0.05)


def test_nvme_offload_trains(tmp_path):
    cfg = _base_config(offload_optimizer={
        "device": "nvme", "nvme_path": str(tmp_path / "swap"),
        "pipeline_read": True})
    engine, losses = _train(cfg, steps=25)
    assert losses[-1] < losses[0] * 0.5, losses
    # moments really live on NVMe
    import os
    assert os.listdir(str(tmp_path / "swap"))


def test_offload_checkpoint_resume(tmp_path):
    cfg = _base_config(offload_optimizer={"device": "cpu"})
    engine, _ = _train(cfg, steps=8)
    engine.save_checkpoint(str(tmp_path / "ck"), tag="t8")

    params2 = simple_model_params(hidden_dim=HIDDEN, nlayers=2, seed=1)
    engine2, _, _, _ = deepspeed_tpu.initialize(
        model=simple_model_loss, model_parameters=params2, config=cfg)
    engine2.load_checkpoint(str(tmp_path / "ck"), tag="t8")
    assert engine2.host_optimizer.step_count == engine.host_optimizer.step_count
    # masters are keyed per (leaf, shard) — compare shard-wise
    for a, b in zip(engine.host_optimizer.master,
                    engine2.host_optimizer.master):
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
    # loss continuity: both engines produce the same next-step loss
    batch = random_batch(8, HIDDEN, seed=9)
    l1 = float(engine.train_batch(batch)["loss"])
    l2 = float(engine2.train_batch(batch)["loss"])
    np.testing.assert_allclose(l1, l2, rtol=0.05, atol=0.02)


def test_sharded_offload_zero3():
    """ZeRO-3 param sharding (fsdp over the 8-device mesh) + host offload:
    masters live per shard, updated leaves are rebuilt onto the mesh
    (multi-host shard handling: only addressable shards are stepped,
    ref: per-DP-rank partitions stage_1_and_2.py:546)."""
    cfg = _base_config(offload_optimizer={"device": "cpu"})
    cfg["zero_optimization"]["stage"] = 3
    cfg["zero_optimization"]["stage3_min_shard_size"] = 1
    engine, losses = _train(cfg, steps=15)
    # at least one leaf should actually be sharded into >1 unique shard
    n_shards = [len(t.by_key) for t in engine.host_optimizer.tables]
    assert max(n_shards) > 1, n_shards
    assert losses[-1] < losses[0] * 0.6, losses
    # parity with the fused (non-offload) stage-3 path
    cfg_dev = _base_config()
    cfg_dev["zero_optimization"]["stage"] = 3
    cfg_dev["zero_optimization"]["stage3_min_shard_size"] = 1
    _, losses_dev = _train(cfg_dev, steps=15)
    np.testing.assert_allclose(losses, losses_dev, rtol=0.25, atol=0.05)


def test_adagrad_offload():
    """Host Adagrad offload (ref: csrc/adagrad/cpu_adagrad.cpp via the
    same offload machinery)."""
    cfg = _base_config(offload_optimizer={"device": "cpu"})
    cfg["optimizer"] = {"type": "adagrad", "params": {"lr": 5e-2}}
    engine, losses = _train(cfg, steps=25)
    assert engine.host_optimizer.optimizer_name == "adagrad"
    assert losses[-1] < losses[0] * 0.7, losses


def test_adagrad_offload_checkpoint_roundtrip(tmp_path):
    """Adagrad offload checkpoints restore (load_state/state_arrays on
    the host adagrad)."""
    cfg = _base_config(offload_optimizer={"device": "cpu"})
    cfg["optimizer"] = {"type": "adagrad", "params": {"lr": 5e-2}}
    engine, _ = _train(cfg, steps=5)
    engine.save_checkpoint(str(tmp_path / "ck"), tag="t5")
    params2 = simple_model_params(hidden_dim=HIDDEN, nlayers=2, seed=1)
    engine2, _, _, _ = deepspeed_tpu.initialize(
        model=simple_model_loss, model_parameters=params2, config=cfg)
    engine2.load_checkpoint(str(tmp_path / "ck"), tag="t5")
    batch = random_batch(8, HIDDEN, seed=9)
    l1 = float(engine.train_batch(batch)["loss"])
    l2 = float(engine2.train_batch(batch)["loss"])
    np.testing.assert_allclose(l1, l2, rtol=0.05, atol=0.02)


def test_sharded_offload_elastic_restore(tmp_path):
    """Moments checkpoint globally (topology-independent): save from a
    sharded stage-3 layout, restore into a DIFFERENT (unsharded stage-1)
    layout — the elastic-checkpoint contract
    (ref: stage_1_and_2.py:2074 _restore_elastic_base_optimizer_state)."""
    cfg3 = _base_config(offload_optimizer={"device": "cpu"})
    cfg3["zero_optimization"]["stage"] = 3
    cfg3["zero_optimization"]["stage3_min_shard_size"] = 1
    engine, _ = _train(cfg3, steps=6)
    assert max(len(t.by_key) for t in engine.host_optimizer.tables) > 1
    engine.save_checkpoint(str(tmp_path / "ck"), tag="t6")

    cfg1 = _base_config(offload_optimizer={"device": "cpu"})
    params2 = simple_model_params(hidden_dim=HIDDEN, nlayers=2, seed=1)
    engine2, _, _, _ = deepspeed_tpu.initialize(
        model=simple_model_loss, model_parameters=params2, config=cfg1)
    engine2.load_checkpoint(str(tmp_path / "ck"), tag="t6")
    # moments restored (non-zero) and step continuity holds
    st = engine2.host_optimizer.state_dict()
    assert any(np.abs(v["exp_avg_sq"]).sum() > 0
               for v in st["state"].values())
    batch = random_batch(8, HIDDEN, seed=9)
    l1 = float(engine.train_batch(batch)["loss"])
    l2 = float(engine2.train_batch(batch)["loss"])
    np.testing.assert_allclose(l1, l2, rtol=0.05, atol=0.02)


def test_shard_export_import_cross_topology():
    """The multi-host checkpoint path: shard pieces exported from a
    sharded (stage-3) layout merge losslessly into a different
    (unsharded stage-1) layout — no zero-filled regions survive."""
    cfg3 = _base_config(offload_optimizer={"device": "cpu"})
    cfg3["zero_optimization"]["stage"] = 3
    cfg3["zero_optimization"]["stage3_min_shard_size"] = 1
    engine, _ = _train(cfg3, steps=5)
    pieces = engine.host_optimizer.shard_export()
    assert len(pieces) > len(engine.host_optimizer.master)  # multi-shard

    cfg1 = _base_config(offload_optimizer={"device": "cpu"})
    params2 = simple_model_params(hidden_dim=HIDDEN, nlayers=2, seed=1)
    engine2, _, _, _ = deepspeed_tpu.initialize(
        model=simple_model_loss, model_parameters=params2, config=cfg1)
    engine2.host_optimizer.shard_import(
        pieces, engine.host_optimizer.step_count)
    # masters identical after merge
    for i in range(len(engine.host_optimizer.master)):
        a = engine.host_optimizer._global_master(i)
        b = engine2.host_optimizer._global_master(i)
        np.testing.assert_array_equal(a, b)
        m1 = engine.host_optimizer._global_moment(i, "exp_avg_sq")
        m2 = engine2.host_optimizer._global_moment(i, "exp_avg_sq")
        np.testing.assert_array_equal(m1, m2)
        assert np.abs(m1).sum() > 0  # moments actually carried over


def test_delayed_param_update():
    """DPU (ZeRO-Offload delayed param update): one-step-stale host Adam
    overlapped with the next step's device work still converges, and
    flush_delayed_update installs the pending update before
    checkpoint/eval."""
    cfg = _base_config(offload_optimizer={"device": "cpu",
                                          "delayed_param_update": True})
    engine, losses = _train(cfg, steps=25)
    assert engine.dpu_enabled
    assert losses[-1] < losses[0] * 0.6, losses
    # pending update exists mid-stream; flush installs it
    step_before = int(engine.state.step)
    engine.flush_delayed_update()
    assert engine._dpu_pending is None
    assert int(engine.state.step) == step_before + 1
    # eval after flush uses current params and is finite
    batch = random_batch(8, HIDDEN, seed=3)
    loss, _ = engine.eval_batch(batch)
    assert np.isfinite(float(loss))


def test_dpu_requires_bf16():
    cfg = _base_config(offload_optimizer={"device": "cpu",
                                          "delayed_param_update": True})
    cfg["bf16"] = {"enabled": False}
    cfg["fp16"] = {"enabled": True}
    params = simple_model_params(hidden_dim=HIDDEN, nlayers=2, seed=0)
    with pytest.raises(ValueError, match="delayed_param_update"):
        deepspeed_tpu.initialize(model=simple_model_loss,
                                 model_parameters=params, config=cfg)


def test_dpu_load_checkpoint_discards_pending(tmp_path):
    """A pending DPU update must never overwrite restored weights."""
    cfg = _base_config(offload_optimizer={"device": "cpu",
                                          "delayed_param_update": True})
    engine, _ = _train(cfg, steps=6)
    engine.save_checkpoint(str(tmp_path / "ck"), tag="t6")  # flushes
    saved = engine.host_optimizer._global_master(0).copy()
    # create a fresh pending update, then load over it
    engine.train_batch(random_batch(8, HIDDEN, seed=7))
    assert engine._dpu_pending is not None
    engine.load_checkpoint(str(tmp_path / "ck"), tag="t6")
    assert engine._dpu_pending is None
    np.testing.assert_array_equal(
        engine.host_optimizer._global_master(0), saved)
    # next step trains from the restored weights, not the stale update
    m = engine.train_batch(random_batch(8, HIDDEN, seed=8))
    assert np.isfinite(float(m["loss"]))


def test_step_pipeline_overlap_schedule():
    """The 3-stage overlap claim, asserted structurally: every shard's
    d2h copy is enqueued BEFORE the first Adam runs, and each leaf's
    updated h2d is in flight before the next leaf's Adam completes
    (ref overlap budget: pipelined_optimizer_swapper.py:60,
    stage_1_and_2.py:1005)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.parallel.mesh import MeshSpec, make_mesh
    from deepspeed_tpu.runtime.zero import offload as off

    mesh = make_mesh(MeshSpec(data=8))
    shard = NamedSharding(mesh, P("data"))
    params = {f"w{i}": np.arange(64, dtype=np.float32) + i
              for i in range(4)}
    shardings = {k: shard for k in params}
    opt = off.HostOffloadOptimizer(params, lr_schedule=lambda s: 1e-2,
                                   shardings=shardings)
    grads = {k: jax.device_put(np.full(64, 0.1, np.float32), shard)
             for k in params}

    events = []
    # the probe is global: a prior test's engine may still flush a DPU
    # background step (ds-dpu thread) — record main-thread events only
    import threading
    main = threading.main_thread()
    off._pipeline_probe = lambda ev, i, k: (
        events.append((ev, i, k))
        if threading.current_thread() is main else None)
    try:
        opt.step(grads)
    finally:
        off._pipeline_probe = None

    d2h = [j for j, e in enumerate(events) if e[0] == "d2h_enqueue"]
    adam = [j for j, e in enumerate(events) if e[0] == "adam_done"]
    assert d2h and adam
    # stage 1 completes before stage 2 starts: transfers all in flight
    assert max(d2h) < min(adam), events[:12]
    # leaf i's h2d enqueued before leaf i+1's first adam completes
    h2d_by_leaf = {}
    adam_first = {}
    for j, (ev, i, k) in enumerate(events):
        if ev == "h2d_enqueue":
            h2d_by_leaf.setdefault(i, j)
        if ev == "adam_done":
            adam_first.setdefault(i, j)
    for i in sorted(h2d_by_leaf)[:-1]:
        assert h2d_by_leaf[i] < adam_first[i + 1], (i, events)


def test_loopback_pipeline_efficiency():
    """The overlap claim enforced at ~0.9 of the measured headline:
    under an emulated serialized link the REAL step schedule must reach
    >=0.85 of the ideal two-stage pipeline bound and come in at <=0.89x
    the no-overlap serial model at two link speeds. (PERF.md headline
    1.11/0.97 eff, 0.53x/0.83x serial at 1/4 GB/s on bigger shards.)
    The clock is the tool's ``ModelledClock``: it advances by the
    link's waits and by a fixed time an update, so both ratios follow
    from the order in which the real ``HostOffloadOptimizer.step``
    enqueues, waits and updates, and not from the wall clock of a CPU
    that the other workers of a whole run share. A schedule that awaited
    each transfer before the next reads 0.93 / 0.97 of serial here and
    fails. Source of truth is the tool's own run() — the same numbers
    its JSON line reports."""
    from tools.offload_loopback import ModelledClock, run as loopback_run
    # link speeds chosen so t_transfer (1.6 / 0.53 ms) is comparable to
    # the update's 2 ms — that's where overlap vs serial actually
    # discriminates (a negligible link makes both models collapse to
    # t_adam)
    for bw in (0.5, 1.5):
        eff, vs_serial = loopback_run(bw, n_leaves=6, elems=200_000,
                                      clock=ModelledClock(0.002))
        assert eff >= 0.85 and vs_serial <= 0.89, (bw, eff, vs_serial)
