"""DeepSpeedEngine — the training engine.

TPU-native analog of the reference engine (ref: deepspeed/runtime/engine.py:168
DeepSpeedEngine; forward :1523, backward :1636, step :1840). The torch
engine mutates module/optimizer state across three calls; under XLA the
whole micro-step pipeline (forward, backward, gradient accumulation,
reduction, overflow check, clip, optimizer update, lr schedule) is ONE
compiled SPMD program: ``train_batch()``. ``forward/backward/step`` wrappers
are provided for API familiarity but delegate to the fused step.

ZeRO stages are realized purely through shardings (see
deepspeed_tpu/parallel/sharding.py): XLA emits the reduce-scatter /
allgather traffic the reference drives by hand with backward hooks
(stage_1_and_2.py:773) and the stage-3 parameter coordinator
(partitioned_param_coordinator.py:45).
"""

import os
import time
from functools import partial
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.ops.adam import adagrad, fused_adam
from deepspeed_tpu.ops.lamb import fused_lamb
from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.parallel import sharding as sharding_lib
from deepspeed_tpu.runtime import constants as C
from deepspeed_tpu.runtime import loss_scaler as ls
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.lr_schedules import get_lr_schedule
from deepspeed_tpu.runtime.utils import (clip_by_global_norm, count_parameters,
                                         global_norm)
from deepspeed_tpu.utils.compile_guard import compile_count
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import (NoopTimer, SynchronizedWallClockTimer,
                                       ThroughputTimer, TRAIN_BATCH_TIMER)

PyTree = Any
LossFn = Callable[..., Any]  # (params, batch, rng) -> loss  or (loss, aux)


class TrainState:
    """Functional train state threaded through the jitted step.

    Registered as a pytree; holds the fp32 master params (ref: the flat
    fp32 groups of FP16_Optimizer / BF16_Optimizer,
    runtime/fp16/fused_optimizer.py:18, runtime/bf16_optimizer.py:75),
    optimizer state, loss-scale state and step counter.
    """

    def __init__(self, step, params, opt_state, scale_state, rng,
                 comm_error=None):
        self.step = step
        self.params = params
        self.opt_state = opt_state
        self.scale_state = scale_state
        self.rng = rng
        # per-DP-rank error-feedback residual for compressed gradient
        # reduction (comm_backend_name="dcn_compressed"; ref: the worker
        # error tensors of NcclBackend.compressed_allreduce, nccl.py:52)
        self.comm_error = comm_error

    def tree_flatten(self):
        return ((self.step, self.params, self.opt_state, self.scale_state,
                 self.rng, self.comm_error), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState,
    lambda s: s.tree_flatten(),
    TrainState.tree_unflatten)


def _cast_tree(tree: PyTree, dtype) -> PyTree:
    def cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree_util.tree_map(cast, tree)


class DeepSpeedEngine:
    """Training engine over one device mesh.

    Parameters
    ----------
    loss_fn : callable(params, batch, rng) -> loss | (loss, aux-dict)
        The model's loss. Computed in the configured precision; params
        arrive already cast to the compute dtype.
    params : pytree of fp32 arrays (the master weights).
    config : DeepSpeedConfig
    mesh : optional prebuilt Mesh (defaults to mesh_from_config).
    partition_rules : optional TP rules (parallel/sharding.PartitionRule).
    optimizer : optional optax.GradientTransformation overriding the config.
    lr_schedule : optional callable(step)->lr overriding the config.
    """

    def __init__(self,
                 loss_fn: LossFn,
                 params: PyTree,
                 config: DeepSpeedConfig,
                 mesh: Optional[Mesh] = None,
                 partition_rules: Optional[Sequence] = None,
                 optimizer: Optional[optax.GradientTransformation] = None,
                 lr_schedule: Optional[Callable] = None,
                 has_aux: bool = False,
                 donate_state: bool = True):
        self.config = config
        self.loss_fn = loss_fn
        self.has_aux = has_aux
        self.mesh = mesh if mesh is not None else mesh_lib.mesh_from_config(config)
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        # compilations inside the train step's call since construction
        # (one for the first step; more means something recompiled)
        self.train_compiles = 0
        self._trace_steps_left = 0
        self.client_lr_schedule = lr_schedule

        self.dp_world_size = mesh_lib.dp_world_size(self.mesh)
        self.mp_world_size = mesh_lib.axis_size(self.mesh, "model")

        # --- elasticity v0.1 enforcement (ref: engine.py:425 + the
        # elastic batch resolution in deepspeed/__init__.py) -----------
        if config.elasticity_enabled:
            from deepspeed_tpu.elasticity import (
                compute_elastic_config, ensure_immutable_elastic_config)
            from deepspeed_tpu.version import __version__ as _ver
            ensure_immutable_elastic_config(config.elasticity_dict)
            # the batch identity is global = micro x gas x DP-replicas,
            # so the validated world is the DP degree; under TP/PP the
            # scheduler's chip count is dp x (mp x pp), and valid_gpus
            # entries denote DP replicas
            final_bs, _valid, _micro = compute_elastic_config(
                {"elasticity": config.elasticity_dict}, _ver,
                world_size=self.dp_world_size)
            if not config.elasticity_dict.get(
                    "ignore_non_elastic_batch_info", False) and \
                    config.train_batch_size != final_bs:
                raise ValueError(
                    f"train_batch_size={config.train_batch_size} conflicts "
                    f"with the elastic batch size {final_bs}; set it to "
                    f"{final_bs} or ignore_non_elastic_batch_info=true")
        from deepspeed_tpu.utils import groups as groups_lib
        groups_lib.set_mesh(self.mesh)

        # --- precision ------------------------------------------------
        self.compute_dtype = config.compute_dtype
        self.fp16_enabled = config.fp16.enabled
        self.bf16_enabled = config.bf16.enabled
        self.dynamic_loss_scale = config.fp16.dynamic_loss_scale
        # memory-efficient bf16: bf16 masters (stochastic-rounding update)
        # + bf16 Adam moments (see BF16Config.memory_efficient)
        self.memory_efficient_bf16 = (config.bf16.enabled
                                      and config.bf16.memory_efficient)
        if config.bf16.memory_efficient and not config.bf16.enabled:
            raise ValueError("bf16.memory_efficient requires bf16.enabled")
        self.master_dtype = (jnp.bfloat16 if self.memory_efficient_bf16
                             else jnp.float32)

        # --- config-driven LoRA (runtime/lora.py) ---------------------
        # adapt BEFORE specs/optimizer so adapter leaves shard and the
        # masked transform sees the final tree
        if config.lora.enabled:
            if config.zero.offload_optimizer.enabled:
                raise ValueError(
                    "lora + offload_optimizer makes no sense: the host "
                    "optimizer exists for multi-GB optimizer state, "
                    "which LoRA removes — drop one of the two")
            from deepspeed_tpu.runtime import lora as lora_lib
            if not isinstance(params.get("block"), dict):
                raise ValueError(
                    "config-driven lora adapts the models/* layout "
                    "(a 'block' dict of dense entries); for a custom "
                    "pytree call runtime.lora.add_lora yourself and "
                    "pass optimizer=lora_optimizer(...)")
            adapted_entries = [e for e in params["block"].values()
                               if isinstance(e, dict) and "lora_a" in e]
            if adapted_entries:
                # resume path: the tree is already adapted — the config
                # knobs must AGREE with it (rank is readable from the
                # adapter shapes; silently training a different rank
                # than the config claims would be worse than an error)
                got_rank = adapted_entries[0]["lora_a"].shape[-1]
                if got_rank != config.lora.rank:
                    raise ValueError(
                        f"params carry rank-{got_rank} adapters but the "
                        f"config says lora.rank={config.lora.rank}")
                got_alpha = float(
                    jnp.ravel(adapted_entries[0]["lora_scale"])[0]
                    * got_rank)
                if abs(got_alpha - config.lora.alpha) > 1e-6:
                    raise ValueError(
                        f"params carry alpha={got_alpha:g} adapters but "
                        f"the config says lora.alpha={config.lora.alpha}")
                got_targets = sorted(
                    n for n, e in params["block"].items()
                    if isinstance(e, dict) and "lora_a" in e)
                want = sorted(n for n in config.lora.targets
                              if n in params["block"])
                if got_targets != want:
                    raise ValueError(
                        f"params adapt {got_targets} but the config's "
                        f"lora.targets resolve to {want}")
            else:
                params = lora_lib.add_lora(
                    params, jax.random.PRNGKey(config.lora.seed),
                    rank=config.lora.rank, alpha=config.lora.alpha,
                    targets=config.lora.targets)
                if not any("lora_a" in e
                           for e in params["block"].values()
                           if isinstance(e, dict)):
                    raise ValueError(
                        f"lora.targets {config.lora.targets} matched no "
                        f"dense entry in the model block "
                        f"({sorted(params['block'])}) — every parameter "
                        f"would be frozen and training would be a no-op")

        # --- shardings ------------------------------------------------
        self.partition_rules = list(partition_rules or [])
        self.param_pspecs = sharding_lib.param_specs(
            params, self.mesh, zero_stage=config.zero.stage,
            rules=self.partition_rules,
            min_shard_size=config.zero.stage3_min_shard_size)
        self.param_shardings = sharding_lib.to_named(self.param_pspecs, self.mesh)

        # --- lr schedule & optimizer ---------------------------------
        self.lr_schedule = self._configure_lr_schedule(lr_schedule)

        # host offload of optimizer state (ZeRO-Offload/Infinity; see
        # runtime/zero/offload.py) — master weights + moments on host,
        # only compute-dtype params on device
        self.offload_enabled = (config.zero.offload_optimizer.enabled
                                and optimizer is None)
        self.dpu_enabled = (self.offload_enabled
                            and config.zero.offload_optimizer
                            .delayed_param_update)
        self._dpu_pending = None
        if self.dpu_enabled:
            if config.fp16.enabled:
                raise ValueError(
                    "delayed_param_update requires bf16 (fp16 overflow "
                    "skipping cannot compose with one-step staleness)")
            import concurrent.futures as _fut
            self._dpu_executor = _fut.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="ds-dpu")
        if self.offload_enabled:
            self._configure_offload_optimizer(params)
            self.optimizer = None
            opt_state = None
            # device_params() already assembles onto the mesh shardings
            params = self.host_optimizer.device_params()
        else:
            params = jax.device_put(_cast_tree(params, self.master_dtype),
                                    self.param_shardings)
            self.optimizer = optimizer if optimizer is not None \
                else self._configure_basic_optimizer()
            if config.lora.enabled:
                from deepspeed_tpu.runtime import lora as lora_lib
                self.optimizer = lora_lib.lora_optimizer(
                    self.optimizer, params)

            # optimizer state: shard like ZeRO stage >= 1
            opt_shape = jax.eval_shape(self.optimizer.init, params)
            self.opt_pspecs = sharding_lib.opt_state_specs(
                opt_shape, self.param_pspecs, params, self.mesh,
                zero_stage=config.zero.stage,
                min_shard_size=config.zero.stage3_min_shard_size)
            self.opt_shardings = sharding_lib.to_named(self.opt_pspecs, self.mesh)
            opt_state = jax.jit(self.optimizer.init,
                                out_shardings=self.opt_shardings)(params)

        scale_state = ls.init_state(
            static_scale=config.fp16.loss_scale if self.fp16_enabled else 1.0,
            initial_scale_power=config.fp16.initial_scale_power,
            hysteresis=config.fp16.hysteresis) if self.fp16_enabled \
            else ls.init_state(static_scale=1.0)

        # --- compressed DP gradient reduction (dcn_compressed) --------
        # the engine-level analog of the reference's compressed allreduce
        # backend (ref: runtime/comm/nccl.py:52): grads cross the wire as
        # packed 1-bit signs + scales with per-rank error feedback
        self.compressed_comm = config.comm_backend_name == "dcn_compressed"
        comm_error = None
        if self.compressed_comm:
            self._validate_compressed_comm()
            comm_error = self._init_comm_error(params)

        rng = jax.random.PRNGKey(config.seed)
        self.state = TrainState(
            step=jnp.zeros([], jnp.int32),
            params=params,
            opt_state=opt_state,
            scale_state=scale_state,
            rng=rng,
            comm_error=comm_error)

        # --- metrics monitor (ref: engine.py:470-517 tensorboard) -----
        if config.tensorboard.enabled:
            from deepspeed_tpu.utils.monitor import Monitor
            self.monitor = Monitor.from_config(config.tensorboard)
        else:
            from deepspeed_tpu.utils.monitor import NoopMonitor
            self.monitor = NoopMonitor()
        self._monitor_buffer = []
        if config.tensorboard.enabled:
            # scalars are buffered between steps_per_print boundaries (a
            # per-step float() would sync the device); make sure a process
            # that never calls destroy() still lands its tail
            import atexit
            atexit.register(self._flush_monitor_buffer)

        # --- timers ---------------------------------------------------
        self.wall_clock_breakdown = config.wall_clock_breakdown
        self.timers = SynchronizedWallClockTimer() if self.wall_clock_breakdown \
            else NoopTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=config.train_batch_size,
            steps_per_output=config.steps_per_print)

        # --- MoQ quantize-aware training (ref: engine.py:1789-1800) ---
        qt = config.quantize_training
        if qt.enabled:
            from deepspeed_tpu.runtime.quantize import Quantizer
            self.quantizer = Quantizer.from_config(qt)
            if qt.eigenvalue.enabled:
                from deepspeed_tpu.runtime.eigenvalue import Eigenvalue
                ecfg = qt.eigenvalue
                self.eigenvalue = Eigenvalue(
                    verbose=ecfg.verbose, max_iter=ecfg.max_iter,
                    tol=ecfg.tol, stability=ecfg.stability,
                    gas_boundary_resolution=ecfg.gas_boundary_resolution,
                    layer_name=ecfg.layer_name, layer_num=ecfg.layer_num)
            else:
                self.eigenvalue = None
        else:
            self.quantizer = None
            self.eigenvalue = None
        self.block_eigenvalue = {}

        def _eigenvalue_loss(p, b, r):
            out = self.loss_fn(p, b, r)
            return out[0] if self.has_aux else out
        # stable identity so Eigenvalue's jitted HVP cache hits
        self._eigenvalue_loss = _eigenvalue_loss

        # --- curriculum learning (ref: engine.py:1548-1554) -----------
        if config.curriculum.enabled:
            from deepspeed_tpu.runtime.data_pipeline import CurriculumScheduler
            cc = config.curriculum
            self.curriculum_scheduler = CurriculumScheduler({
                "curriculum_type": cc.curriculum_type,
                "min_difficulty": cc.min_difficulty,
                "max_difficulty": cc.max_difficulty,
                "schedule_type": cc.schedule_type,
                "schedule_config": cc.schedule_config})
        else:
            self.curriculum_scheduler = None

        # --- progressive layer drop (ref: engine.py:1542) -------------
        if config.pld.enabled:
            from deepspeed_tpu.runtime.progressive_layer_drop import (
                ProgressiveLayerDrop)
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=config.pld.theta, gamma=config.pld.gamma)
        else:
            self.progressive_layer_drop = None

        # --- compiled programs ---------------------------------------
        self._donate_state = donate_state
        if self.offload_enabled:
            self._train_step = None
            self._grad_step = self._build_grad_step()
        else:
            self._train_step = self._build_train_step(donate_state)
            # commit every leaf, the scalar step/scale/rng included, to
            # its mesh sharding now: an uncommitted first-call argument
            # has another type than the step's own output, and the second
            # step would trace and compile the whole program again
            self.state = jax.device_put(self.state, self._state_shardings)
        self._eval_step = self._build_eval_step()

        n_params = count_parameters(params)
        # what the model says of itself (models/gpt.py make_loss_fn: the
        # attention implementation its step compiles on this platform, and
        # the loss's layout on this mesh)
        describe = getattr(loss_fn, "describe", None)
        with jax.set_mesh(self.mesh):
            model_says = "".join(
                f", {k}={v}" for k, v in describe().items()) \
                if describe is not None else ""
        log_dist(
            f"engine ready: {n_params / 1e6:.2f}M params, zero_stage="
            f"{config.zero.stage}, precision={config.precision_name}, "
            f"dp={self.dp_world_size}, tp={self.mp_world_size}, "
            f"micro_bs={config.train_micro_batch_size_per_gpu}, "
            f"gas={config.gradient_accumulation_steps}, "
            f"platform={self.mesh.devices.flat[0].platform}{model_says}",
            ranks=[0])
        self._warn_hbm_headroom(n_params)

    def _warn_hbm_headroom(self, n_params: int) -> None:
        """Best-effort warning when the per-device TRAINING STATE alone
        (params + optimizer moments [+ masters] + a gradient buffer) sits
        within the compile-headroom of device HBM — borderline-HBM
        programs put this backend's compiler into a multi-minute fitting
        grind (see utils/hbm.py and PERF.md). State is the part the
        engine can compute without knowing the model architecture;
        activations come on top, so a warning here means near-certain
        trouble. Never raises: the user may know better."""
        if (self.offload_enabled or self.config.zero.offload_param.enabled):
            return  # moments/params live on host — state model doesn't apply
        from deepspeed_tpu.utils import hbm as hbm_guard
        try:
            cap = hbm_guard.device_hbm_bytes(self.mesh.devices.flat[0]
                                             if self.mesh is not None
                                             else None)
        except Exception:
            cap = None
        if cap is None:
            return
        sb = hbm_guard.state_bytes(
            n_params, self.config.precision_name,
            self.config.bf16.memory_efficient,
            (self.config.optimizer.type or "").lower())
        # TP shards every tensor over 'model'; ZeRO shards optimizer
        # (stage>=1), grads (>=2) and params (>=3) over data/fsdp
        tp = max(1, self.mp_world_size)
        shards = max(1, self.dp_world_size)
        pb = 4 if self.config.precision_name == "fp32" else 2
        state = sb["params"] // tp
        if self.config.zero.stage >= 3:
            state //= shards
        state += sb["optimizer"] // tp // (shards if self.config.zero.stage
                                           >= 1 else 1)
        state += n_params * pb // tp // (shards if self.config.zero.stage
                                         >= 2 else 1)  # gradient buffer
        limit = cap - int(hbm_guard.DEFAULT_HEADROOM_GIB * hbm_guard.GiB)
        if state > limit:
            logger.warning(
                f"training state alone is ~{state / hbm_guard.GiB:.1f}GiB "
                f"per device vs {cap / hbm_guard.GiB:.0f}GiB HBM "
                f"(compile-safe limit {limit / hbm_guard.GiB:.1f}GiB, "
                f"before activations) — expect OOM or a pathological "
                f"borderline-HBM compile. Consider zero stage 3 over more "
                f"devices, bf16.memory_efficient, or offload.")

    # ------------------------------------------------------------------
    # configuration
    # ------------------------------------------------------------------
    def _configure_lr_schedule(self, override):
        if override is not None:
            return override
        base_lr = (self.config.optimizer.params or {}).get("lr", 1e-3)
        sched_cfg = self.config.scheduler
        return get_lr_schedule(sched_cfg.type, sched_cfg.params, base_lr=base_lr)

    def _configure_basic_optimizer(self) -> optax.GradientTransformation:
        """Config-name -> optimizer (ref: engine.py:1108
        _configure_basic_optimizer)."""
        ocfg = self.config.optimizer
        name = (ocfg.type or C.ADAMW_OPTIMIZER).lower()
        p = dict(ocfg.params or {})
        lr = self.lr_schedule
        betas = p.get("betas", (0.9, 0.999))
        eps = p.get("eps", 1e-8)
        wd = p.get("weight_decay", 0.0)

        if name in (C.ADAM_OPTIMIZER, C.ADAMW_OPTIMIZER, C.FUSED_ADAM_OPTIMIZER,
                    C.CPU_ADAM_OPTIMIZER):
            adam_w_mode = p.get("adam_w_mode", name != C.ADAM_OPTIMIZER or wd == 0.0)
            if name == C.ADAMW_OPTIMIZER:
                adam_w_mode = True
            return fused_adam(lr, b1=betas[0], b2=betas[1], eps=eps,
                              weight_decay=wd, adam_w_mode=adam_w_mode,
                              state_dtype=(jnp.bfloat16 if
                                           self.memory_efficient_bf16
                                           else None))
        if self.memory_efficient_bf16:
            raise ValueError(
                "bf16.memory_efficient supports the Adam family only "
                f"(got optimizer {name!r})")
        if name in (C.LAMB_OPTIMIZER, C.FUSED_LAMB_OPTIMIZER):
            return fused_lamb(lr, b1=betas[0], b2=betas[1],
                              eps=p.get("eps", 1e-6), weight_decay=wd,
                              max_coeff=p.get("max_coeff", 10.0),
                              min_coeff=p.get("min_coeff", 0.01))
        if name == C.SGD_OPTIMIZER:
            return optax.chain(
                optax.trace(decay=p.get("momentum", 0.0), nesterov=p.get("nesterov", False)),
                optax.scale_by_schedule(lambda c: -lr(c)) if callable(lr) else optax.scale(-lr))
        if name == C.ADAGRAD_OPTIMIZER:
            return adagrad(lr, eps=eps, weight_decay=wd)
        if name in (C.ONEBIT_ADAM_OPTIMIZER, C.ONEBIT_LAMB_OPTIMIZER,
                    C.ZERO_ONE_ADAM_OPTIMIZER):
            from deepspeed_tpu.runtime.comm.onebit import (onebit_adam,
                                                           onebit_lamb,
                                                           zero_one_adam)
            factory = {C.ONEBIT_ADAM_OPTIMIZER: onebit_adam,
                       C.ONEBIT_LAMB_OPTIMIZER: onebit_lamb,
                       C.ZERO_ONE_ADAM_OPTIMIZER: zero_one_adam}[name]
            return factory(lr, config_params=p)
        raise ValueError(f"unknown optimizer {name}")

    def _configure_offload_optimizer(self, params: PyTree):
        """Build the host-resident optimizer for ZeRO-Offload/Infinity
        (ref: stage_1_and_2.py:1725 CPU Adam step path; NVMe via
        swap_tensor swappers). Master fp32 weights + moments live on host;
        see runtime/zero/offload.py for the architecture."""
        from deepspeed_tpu.runtime.zero.offload import HostOffloadOptimizer
        ocfg = self.config.optimizer
        name = (ocfg.type or C.ADAMW_OPTIMIZER).lower()
        if name not in (C.ADAM_OPTIMIZER, C.ADAMW_OPTIMIZER,
                        C.FUSED_ADAM_OPTIMIZER, C.CPU_ADAM_OPTIMIZER,
                        C.ADAGRAD_OPTIMIZER):
            raise ValueError(
                "offload_optimizer supports the Adam family and Adagrad, "
                f"got {name}")
        p = dict(ocfg.params or {})
        off = self.config.zero.offload_optimizer
        nvme = off.nvme_path if off.device == C.OFFLOAD_DEVICE_NVME else None
        if off.device == C.OFFLOAD_DEVICE_NVME and nvme is None:
            raise ValueError("offload_optimizer.device=nvme needs nvme_path")
        self.host_optimizer = HostOffloadOptimizer(
            params, self.lr_schedule,
            betas=tuple(p.get("betas", (0.9, 0.999))),
            eps=p.get("eps", 1e-8),
            weight_decay=p.get("weight_decay", 0.0),
            adamw_mode=p.get("adam_w_mode", True) or name == C.ADAMW_OPTIMIZER,
            nvme_path=nvme,
            pipeline_swap=off.pipeline_read or off.pipeline_write,
            param_dtype=self.compute_dtype,
            shardings=self.param_shardings,
            optimizer=("adagrad" if name == C.ADAGRAD_OPTIMIZER
                       else "adam"))

    # ------------------------------------------------------------------
    # compressed DP gradient reduction (comm_backend_name="dcn_compressed")
    # ------------------------------------------------------------------
    def _validate_compressed_comm(self) -> None:
        """Compressed reduction covers plain data parallelism with ZeRO
        stage <= 2 — one stage BEYOND the reference's 1-bit backends
        (stage <= 1, ref: onebit docs + stage checks in
        runtime/fp16/onebit/adam.py): stage 2's gradient partitioning
        dissolves here (the sharded optimizer update consumes its slice
        of the compressed-averaged gradient in the auto domain, outside
        the manual-'data' shard_map), so per-rank gradients stay whole
        exactly as error feedback requires. Stage 3 composes via the
        PERF.md scheme ('Compressed DCN x ZeRO-fsdp'): the 'fsdp' axis
        stays AUTO inside the manual-'data' shard_map, so XLA keeps the
        exact per-layer param gathers and the exact gradient
        reduce-scatter over fsdp/ICI, while the manual wire carries
        1-bit payloads of each device's 1/fsdp grad shard across
        'data'/DCN — compression and sharding multiply (per-rank DCN
        bytes P/(8*fsdp); ref scope: the reference's 1-bit backends
        stop at stage 1, runtime/fp16/onebit/adam.py:14)."""
        for axis in ("model", "pipe", "sequence"):
            if mesh_lib.axis_size(self.mesh, axis) > 1:
                raise ValueError(
                    f"dcn_compressed composes with data/fsdp parallelism "
                    f"only; mesh axis '{axis}' has size > 1")
        if (self.config.zero.stage == 3
                and mesh_lib.axis_size(self.mesh, "data") == 1):
            raise ValueError(
                "dcn_compressed with zero stage 3 requires "
                "mesh.replica_parallel_size > 1: with a single replica "
                "there is no cross-replica ('data') axis to compress — "
                "1-bit noise over the exact fsdp arithmetic is pure loss "
                "(PERF.md 'Compressed DCN x ZeRO-fsdp')")
        if self.offload_enabled:
            raise ValueError("dcn_compressed and offload_optimizer are "
                             "mutually exclusive")

    def _init_comm_error(self, params: PyTree) -> PyTree:
        """Per-replica error-feedback residuals: leaf shape
        [n_data, *param]; leading dim sharded over 'data' so each
        replica holds one param-shaped fp32 residual (ref: the
        worker_error buffers of nccl.py compressed_allreduce). Under
        ZeRO-3 the param dims additionally keep the leaf's fsdp
        sharding — each DEVICE then holds exactly the residual for its
        own 1/fsdp grad shard, and nothing is replicated."""
        ndata = mesh_lib.axis_size(self.mesh, "data")

        def err_sharding(psp):
            return NamedSharding(self.mesh, P("data", *tuple(psp)))

        def make(p, psp):
            return jax.device_put(
                jnp.zeros((ndata,) + tuple(p.shape), jnp.float32),
                err_sharding(psp))

        return jax.tree_util.tree_map(make, params, self.param_pspecs)

    def _comm_error_shardings(self) -> PyTree:
        return jax.tree_util.tree_map(
            lambda psp: NamedSharding(self.mesh, P("data", *tuple(psp))),
            self.param_pspecs)

    # ------------------------------------------------------------------
    # compiled step construction
    # ------------------------------------------------------------------
    def _build_train_step(self, donate_state: bool):
        cfg = self.config
        gas = cfg.gradient_accumulation_steps
        clip = cfg.gradient_clipping
        fp16 = self.fp16_enabled
        compute_dtype = self.compute_dtype
        loss_fn = self.loss_fn
        has_aux = self.has_aux
        optimizer = self.optimizer
        prescale = cfg.prescale_gradients
        predivide = cfg.gradient_predivide_factor

        # MoQ: fake-quantize the compute-dtype copy inside the step; the
        # fp32 masters stay full precision (ref: engine.py:1789-1800
        # quantizes optimizer.bit16_groups, not the fp32 masters)
        quant_fn = self.quantizer.make_transform(
            step_at_build=self.global_steps - self.skipped_steps) \
            if (self.quantizer is not None and self.quantizer.active) else None
        pld_cfg = cfg.pld if cfg.pld.enabled else None

        def micro_loss(params, micro_batch, rng, scale_state, step):
            cparams = _cast_tree(params, compute_dtype)
            if quant_fn is not None:
                rng, qr = jax.random.split(rng)
                cparams = quant_fn(cparams, qr, step)
            # cast float inputs too (ref: engine.py:951 half()/bfloat16() cast
            # of module AND inputs) so activations genuinely run on the MXU in
            # the reduced precision
            micro_batch = _cast_tree(micro_batch, compute_dtype)
            if pld_cfg is not None and isinstance(micro_batch, dict):
                # PLD keep-prob: a pure function of the step counter,
                # threaded as a traced scalar (ref: engine.py:1542 injects
                # it as a fwd kwarg host-side)
                from deepspeed_tpu.runtime.progressive_layer_drop import (
                    PLD_THETA_KEY, theta_schedule)
                micro_batch = dict(micro_batch)
                micro_batch[PLD_THETA_KEY] = theta_schedule(
                    step, pld_cfg.theta, pld_cfg.gamma)
            with jax.named_scope("loss"):
                out = loss_fn(cparams, micro_batch, rng)
            if has_aux:
                loss, aux = out
            else:
                loss, aux = out, {}
            scaled = ls.scale_loss(loss.astype(jnp.float32), scale_state) if fp16 else loss
            return scaled.astype(jnp.float32), (loss, aux)

        grad_fn = jax.grad(micro_loss, has_aux=True)

        compressed = self.compressed_comm
        mesh = self.mesh

        def accum_grads(params, batch, step_rng, scale_state, step):
            """Gradient accumulation over microbatches (lax.scan).
            Under jit the batch's data sharding makes XLA emit the DP
            reduction; inside shard_map (compressed path) it yields the
            rank-local gradients."""
            def micro_body(carry, micro):
                grads_acc, loss_acc, r = carry
                r, mr = jax.random.split(r)
                g, (loss, _aux) = grad_fn(params, micro, mr,
                                          scale_state, step)
                if prescale and predivide != 1.0:
                    g = jax.tree_util.tree_map(lambda x: x / predivide, g)
                grads_acc = jax.tree_util.tree_map(
                    lambda a, b: (a + b.astype(a.dtype)), grads_acc, g)
                return (grads_acc, loss_acc + loss.astype(jnp.float32), r), None

            # memory-efficient mode keeps the accumulator in bf16 (half
            # the transient grad memory — what lets 1.5B-class training
            # state + grads fit one 16GB chip); gas is typically 1 there,
            # so fp32 accumulation buys nothing
            acc_dtype = (jnp.bfloat16 if self.memory_efficient_bf16
                         else jnp.float32)
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, acc_dtype), params)
            if gas > 1:
                micro_batches = jax.tree_util.tree_map(
                    lambda x: x.reshape((gas, x.shape[0] // gas) + x.shape[1:]),
                    batch)
                (grads, loss_sum, _), _ = jax.lax.scan(
                    micro_body,
                    (zeros, jnp.zeros([], jnp.float32), step_rng),
                    micro_batches)
            else:
                (grads, loss_sum, _), _ = micro_body(
                    (zeros, jnp.zeros([], jnp.float32), step_rng), batch)
            mean_loss = loss_sum / gas
            grads = jax.tree_util.tree_map(lambda g: g / gas, grads)
            return grads, mean_loss

        def compressed_grads(params, batch, step_rng, scale_state, step,
                             comm_error):
            """Per-rank grads + 1-bit error-feedback allreduce over 'data'
            inside shard_map — the wire carries packed uint8 signs + one
            f32 scale per leaf (ref: nccl.py:52 compressed_allreduce)."""
            from deepspeed_tpu.parallel.compressed import (
                compressed_allreduce_local)
            err_leaves, err_treedef = jax.tree_util.tree_flatten(comm_error)

            def local_fn(params, batch, comm_error_leaves):
                # decorrelate per-rank dropout/rng
                local_rng = jax.random.fold_in(
                    step_rng, jax.lax.axis_index("data"))
                local_grads, local_loss = accum_grads(
                    params, batch, local_rng, scale_state, step)
                if fp16:
                    local_grads = ls.unscale_grads(local_grads, scale_state)
                    # overflow must be caught BEFORE compression — an inf
                    # gradient would poison the error residual (inf - inf)
                    # for every later step (ref checks overflow pre-compress)
                    ovf = jax.lax.pmax(
                        ls.has_overflow(local_grads).astype(jnp.float32),
                        "data") > 0
                else:
                    ovf = jnp.asarray(False)
                g_leaves = jax.tree_util.tree_leaves(local_grads)
                outs, new_errs = [], []
                for g, e in zip(g_leaves, comm_error_leaves):
                    g = jnp.where(ovf, jnp.zeros_like(g), g)
                    avg, ne = compressed_allreduce_local(
                        g, e[0], axis="data")
                    outs.append(avg)
                    new_errs.append(jnp.where(ovf, e[0], ne)[None])
                loss = jax.lax.pmean(local_loss, "data")
                return tuple(outs), tuple(new_errs), loss, ovf

            gspecs = tuple(P() for _ in err_leaves)
            espec = tuple(P("data") for _ in err_leaves)
            pspec = jax.tree_util.tree_map(lambda _: P(), params)
            bspec = jax.tree_util.tree_map(lambda _: P("data"), batch)
            out = jax.shard_map(
                local_fn, mesh=mesh,
                in_specs=(pspec, bspec, espec),
                out_specs=(gspecs, espec, P(), P()),
                axis_names={"data"}, check_vma=False)(
                    params, batch, tuple(err_leaves))
            g_flat, e_flat, mean_loss, ovf = out
            grads = jax.tree_util.tree_unflatten(err_treedef, list(g_flat))
            new_error = jax.tree_util.tree_unflatten(err_treedef,
                                                     list(e_flat))
            return grads, mean_loss, new_error, ovf

        mem_eff = self.memory_efficient_bf16

        def step_fn(state: TrainState, batch: PyTree):
            rng, step_rng = jax.random.split(state.rng)

            if compressed:
                grads, mean_loss, new_comm_error, overflow = compressed_grads(
                    state.params, batch, step_rng, state.scale_state,
                    state.step, state.comm_error)
            else:
                grads, mean_loss = accum_grads(
                    state.params, batch, step_rng, state.scale_state,
                    state.step)
                new_comm_error = state.comm_error
                # ---- unscale + overflow check (fp16) ----
                if fp16:
                    grads = ls.unscale_grads(grads, state.scale_state)
                    overflow = ls.has_overflow(grads)
                else:
                    overflow = jnp.asarray(False)

            with jax.named_scope("grad_norm"):
                gnorm = global_norm(grads)
                if clip > 0.0:
                    grads = clip_by_global_norm(grads, clip, norm=gnorm)

            # ---- optimizer update with overflow skip (lax.cond) ----
            def do_step(operands):
                g, os_, p = operands
                updates, new_os = optimizer.update(g, os_, p)
                if mem_eff:
                    # bf16 masters: stochastic-rounding add so sub-ulp
                    # updates land in expectation (ops/adam.py)
                    from deepspeed_tpu.ops.adam import sr_apply_updates
                    new_p = sr_apply_updates(
                        p, updates, jax.random.fold_in(step_rng, 0x5eed))
                else:
                    new_p = optax.apply_updates(p, updates)
                return new_os, new_p

            def skip_step(operands):
                _, os_, p = operands
                return os_, p

            with jax.named_scope("optimizer"):
                new_opt_state, new_params = jax.lax.cond(
                    overflow, skip_step, do_step,
                    (grads, state.opt_state, state.params))

            new_scale = ls.update(
                state.scale_state, overflow,
                dynamic=self.dynamic_loss_scale and fp16,
                scale_window=cfg.fp16.loss_scale_window,
                min_scale=cfg.fp16.min_loss_scale,
                max_hysteresis=cfg.fp16.hysteresis)

            new_state = TrainState(
                step=state.step + jnp.where(overflow, 0, 1),
                params=new_params,
                opt_state=new_opt_state,
                scale_state=new_scale,
                rng=rng,
                comm_error=new_comm_error)
            metrics = {
                "loss": mean_loss,
                "grad_norm": gnorm,
                "lr": jnp.asarray(self.lr_schedule(state.step), jnp.float32),
                "loss_scale": new_scale.loss_scale,
                "overflow": overflow,
            }
            return new_state, metrics

        state_shardings = TrainState(
            step=NamedSharding(self.mesh, P()),
            params=self.param_shardings,
            opt_state=self.opt_shardings,
            scale_state=jax.tree_util.tree_map(
                lambda _: NamedSharding(self.mesh, P()), self.state.scale_state),
            rng=NamedSharding(self.mesh, P()),
            comm_error=(self._comm_error_shardings()
                        if self.compressed_comm else None))
        metrics_sh = NamedSharding(self.mesh, P())

        self._state_shardings = state_shardings
        self._batch_shard_leaf = mesh_lib.batch_sharding(self.mesh)
        # an explicit module name (jit_train_step): profiles and the
        # provenance table keep it when the function is renamed
        step_fn.__name__ = step_fn.__qualname__ = "train_step"
        return jax.jit(
            step_fn,
            in_shardings=(state_shardings, None),  # batch: committed by _shard_batch
            out_shardings=(state_shardings, metrics_sh),
            donate_argnums=(0,) if donate_state else ())

    def _build_grad_step(self):
        """Grad-only program for the offload path: forward+backward+clip on
        device; the optimizer update happens on host (runtime/zero/offload)."""
        cfg = self.config
        gas = cfg.gradient_accumulation_steps
        clip = cfg.gradient_clipping
        fp16 = self.fp16_enabled
        compute_dtype = self.compute_dtype
        loss_fn = self.loss_fn
        has_aux = self.has_aux
        prescale = cfg.prescale_gradients
        predivide = cfg.gradient_predivide_factor

        # MoQ + PLD compose with offload exactly as with the fused step:
        # both only transform the in-jit FORWARD (fake-quantized compute
        # params / theta-scheduled layer drop) — the host optimizer never
        # sees them (ref: engine.py:1789-1800 + :1542 compose with
        # cpu_offload the same way)
        quant_fn = self.quantizer.make_transform(
            step_at_build=self.global_steps - self.skipped_steps) \
            if (self.quantizer is not None and self.quantizer.active) else None
        pld_cfg = cfg.pld if cfg.pld.enabled else None

        def micro_loss(params, micro_batch, rng, scale_state, step):
            cparams = _cast_tree(params, compute_dtype)
            if quant_fn is not None:
                rng, qr = jax.random.split(rng)
                cparams = quant_fn(cparams, qr, step)
            micro_batch = _cast_tree(micro_batch, compute_dtype)
            if pld_cfg is not None and isinstance(micro_batch, dict):
                from deepspeed_tpu.runtime.progressive_layer_drop import (
                    PLD_THETA_KEY, theta_schedule)
                micro_batch = dict(micro_batch)
                micro_batch[PLD_THETA_KEY] = theta_schedule(
                    step, pld_cfg.theta, pld_cfg.gamma)
            out = loss_fn(cparams, micro_batch, rng)
            loss, aux = out if has_aux else (out, {})
            scaled = ls.scale_loss(loss.astype(jnp.float32), scale_state) \
                if fp16 else loss
            return scaled.astype(jnp.float32), (loss, aux)

        grad_fn = jax.grad(micro_loss, has_aux=True)

        def gstep(params, batch, rng, scale_state, step):
            rng, step_rng = jax.random.split(rng)

            def micro_body(carry, micro):
                grads_acc, loss_acc, r = carry
                r, mr = jax.random.split(r)
                g, (loss, _aux) = grad_fn(params, micro, mr, scale_state,
                                          step)
                if prescale and predivide != 1.0:
                    g = jax.tree_util.tree_map(lambda x: x / predivide, g)
                grads_acc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), grads_acc, g)
                return (grads_acc, loss_acc + loss.astype(jnp.float32), r), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            if gas > 1:
                micro_batches = jax.tree_util.tree_map(
                    lambda x: x.reshape((gas, x.shape[0] // gas) + x.shape[1:]),
                    batch)
                (grads, loss_sum, _), _ = jax.lax.scan(
                    micro_body, (zeros, jnp.zeros([], jnp.float32), step_rng),
                    micro_batches)
            else:
                (grads, loss_sum, _), _ = micro_body(
                    (zeros, jnp.zeros([], jnp.float32), step_rng), batch)

            grads = jax.tree_util.tree_map(lambda g: g / gas, grads)
            if fp16:
                grads = ls.unscale_grads(grads, scale_state)
                overflow = ls.has_overflow(grads)
            else:
                overflow = jnp.asarray(False)
            with jax.named_scope("grad_norm"):
                gnorm = global_norm(grads)
                if clip > 0.0:
                    grads = clip_by_global_norm(grads, clip, norm=gnorm)
            new_scale = ls.update(
                scale_state, overflow,
                dynamic=self.dynamic_loss_scale and fp16,
                scale_window=cfg.fp16.loss_scale_window,
                min_scale=cfg.fp16.min_loss_scale,
                max_hysteresis=cfg.fp16.hysteresis)
            metrics = {"loss": loss_sum / gas, "grad_norm": gnorm,
                       "overflow": overflow,
                       "loss_scale": new_scale.loss_scale}
            return grads, rng, new_scale, metrics

        rep = NamedSharding(self.mesh, P())
        scale_sh = jax.tree_util.tree_map(lambda _: rep,
                                          self.state.scale_state)
        self._state_shardings = TrainState(
            step=rep, params=self.param_shardings, opt_state=None,
            scale_state=scale_sh, rng=rep)
        self._batch_shard_leaf = mesh_lib.batch_sharding(self.mesh)
        return jax.jit(
            gstep,
            in_shardings=(self.param_shardings, None, rep, scale_sh, rep),
            out_shardings=(self.param_shardings, rep, scale_sh, rep))

    def _offload_train_batch(self, batch: PyTree) -> Dict[str, jnp.ndarray]:
        grads, rng, new_scale, metrics = self._grad_step(
            self.state.params, batch, self.state.rng, self.state.scale_state,
            jnp.asarray(int(self.state.step), jnp.int32))
        self.state.rng = rng
        self.state.scale_state = new_scale
        if self.dpu_enabled:
            # delayed param update (ZeRO-Offload DPU): the grad program
            # for THIS batch was dispatched with the previous params;
            # install the overlapped update from the last step, then hand
            # this step's grads to the worker — the host Adam runs behind
            # the device's next forward/backward at one step of staleness
            if self._dpu_pending is not None:
                self.state.params = self._dpu_pending.result()
                self.state.step = self.state.step + 1
            lr = float(self.lr_schedule(int(self.state.step)))
            self._dpu_pending = self._dpu_executor.submit(
                self.host_optimizer.step, grads, lr)
        elif not bool(metrics["overflow"]):
            # pipelined shard-wise d2h -> host native optimizer -> h2d;
            # the returned tree is already placed on the mesh
            # (ref: stage_1_and_2.py:1005,1725)
            self.state.params = self.host_optimizer.step(
                grads, lr=float(self.lr_schedule(int(self.state.step))))
            self.state.step = self.state.step + 1
        metrics["lr"] = jnp.asarray(self.lr_schedule(int(self.state.step)),
                                    jnp.float32)
        return metrics

    def flush_delayed_update(self) -> None:
        """Join a pending DPU host step (call before checkpointing or
        evaluation so the installed params are current)."""
        if getattr(self, "_dpu_pending", None) is not None:
            self.state.params = self._dpu_pending.result()
            self.state.step = self.state.step + 1
            self._dpu_pending = None

    def _shard_batch(self, batch: PyTree) -> PyTree:
        """Place a host batch on the mesh: leading dim over the dp axes,
        token dim over 'sequence' when sequence parallelism is active."""
        shardings = jax.tree_util.tree_map(self._batch_shard_leaf, batch)
        return jax.device_put(batch, shardings)

    def _build_eval_step(self):
        compute_dtype = self.compute_dtype
        # 1F1B pipeline losses run fwd+bwd eagerly inside their forward
        # (custom_vjp) — they attach an eval-safe GPipe companion
        loss_fn = getattr(self.loss_fn, "eval_fn", None) or self.loss_fn
        has_aux = self.has_aux

        def eval_fn(params, batch, rng):
            cparams = _cast_tree(params, compute_dtype)
            out = loss_fn(cparams, batch, rng)
            return out if has_aux else (out, {})

        return jax.jit(
            eval_fn,
            in_shardings=(self.param_shardings, None, None),
            out_shardings=NamedSharding(self.mesh, P()))

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def start_trace(self, log_dir: str, steps: int = 1) -> None:
        """Capture an XPlane trace of the next ``steps`` train_batch calls
        into ``log_dir`` (TensorBoard/xprof readable) — the runtime analog
        of the reference's NVTX+nsight workflow (ref: utils/nvtx.py:4,
        docs/_tutorials/pytorch-profiler.md). See utils/trace.py."""
        jax.block_until_ready(self.state.params)  # trace only the window
        jax.profiler.start_trace(log_dir)
        self._trace_steps_left = max(1, int(steps))

    def train_batch(self, batch: PyTree) -> Dict[str, jnp.ndarray]:
        """One full optimizer step over a global batch
        (leading dim == train_batch_size). Fuses the reference's
        forward+backward+step triple into one XLA program."""
        # bare profiler annotations (no telemetry bundle here): in a
        # profile, train.step carries the step number and splits into
        # batch placement, the jitted call and the host work after it;
        # with no profiler session each costs tens of nanoseconds
        with jax.profiler.StepTraceAnnotation(
                "train.step", step_num=self.global_steps):
            with jax.profiler.TraceAnnotation("train.input"):
                self.tput_timer.start()
                self.timers(TRAIN_BATCH_TIMER).start()
                if self.curriculum_scheduler is not None:
                    difficulty = self.curriculum_scheduler.update_difficulty(
                        self.global_steps + 1)
                    batch = self._apply_curriculum(batch, difficulty)
                if self.progressive_layer_drop is not None:
                    # keyed on applied steps, matching the in-jit
                    # theta_schedule even when fp16 overflow skips steps;
                    # computed host-side (global - skipped) to avoid
                    # syncing on state.step
                    self.progressive_layer_drop.update_state(
                        self.global_steps - self.skipped_steps)
                batch = self._shard_batch(batch)
            profiling_now = (self.config.flops_profiler.enabled
                             and not self.offload_enabled
                             and self.global_steps + 1 ==
                             self.config.flops_profiler.profile_step)
            if profiling_now:
                # drain queued prior steps so the timed window is exactly
                # this step (set profile_step >= 2 to exclude compile time)
                jax.block_until_ready(self.state.params)
            t0 = time.perf_counter()
            compiles = compile_count()
            # mesh in context: models can pin activation layouts with bare
            # PartitionSpecs (gpt.py scan-carry constraint) during tracing
            with jax.profiler.TraceAnnotation("train.dispatch"), \
                    jax.set_mesh(self.mesh):
                if self.offload_enabled:
                    metrics = self._offload_train_batch(batch)
                else:
                    self.state, metrics = self._train_step(self.state, batch)
            # a step that compiles again is visible from inside (PR 21
            # found one compiling twice)
            self.train_compiles += compile_count() - compiles
            with jax.profiler.TraceAnnotation("train.host"):
                self._after_dispatch(batch, metrics, profiling_now, t0)
        if self._trace_steps_left > 0:      # a start_trace window
            self._trace_steps_left -= 1
            if self._trace_steps_left == 0:
                jax.block_until_ready(metrics["loss"])
                jax.profiler.stop_trace()
        return metrics

    def _after_dispatch(self, batch: PyTree, metrics, profiling_now: bool,
                        t0: float) -> None:
        """The host work of ``train_batch`` after the jitted call: the
        flops profile, timers, counters, the monitor."""
        if profiling_now:
            # block only on the profiled step — every other step keeps
            # async dispatch so the host can run ahead
            jax.block_until_ready(metrics["loss"])
        self._last_step_duration = time.perf_counter() - t0
        if profiling_now:
            self._run_flops_profile(batch)
        self.timers(TRAIN_BATCH_TIMER).stop()
        self.tput_timer.stop(global_step=True)
        if self.quantizer is not None:
            self._take_quantize_step(batch, bool(metrics["overflow"]))
        self.global_steps += 1
        self.micro_steps += self.config.gradient_accumulation_steps
        self.global_samples += self.config.train_batch_size
        # Overflow (and therefore step-skipping) only exists under fp16 loss
        # scaling; in bf16/fp32 the in-jit flag is constant False. Reading it
        # host-side would force a device sync every step — on a remote-dispatch
        # TPU runtime that is a full RPC roundtrip that serializes the
        # pipeline (the reference pays the same sync in its per-step
        # check_overflow allreduce, stage_1_and_2.py:1640; we only pay it when
        # the feature is actually on).
        if self.fp16_enabled and bool(metrics["overflow"]):
            self.skipped_steps += 1
        if self.monitor.enabled:
            # scalar names mirror the reference's tensorboard tags
            # (ref: engine.py:1656-1666, :1889-1917). Buffer the device
            # scalars and convert only at flush boundaries — float() every
            # step would block on the device and defeat async dispatch.
            self._monitor_buffer.append(
                (self.global_samples, metrics["loss"], metrics["lr"],
                 metrics["loss_scale"]))
            if (self.global_steps % self.config.steps_per_print == 0
                    or len(self._monitor_buffer) >= 64):
                self._flush_monitor_buffer()
        if self.global_steps % self.config.steps_per_print == 0:
            self._report_progress(metrics)

    def _flush_monitor_buffer(self):
        buffered, self._monitor_buffer = self._monitor_buffer, []
        if not buffered:
            return
        # ONE device_get for the whole buffer: three float() per buffered
        # step would issue 3*len(buffered) blocking transfers (each a full
        # RPC roundtrip on a remote-dispatch runtime); fetching the pytree
        # at once pays a single sync for the flush
        scalars = jax.device_get([(loss, lr, scale)
                                  for _, loss, lr, scale in buffered])
        events = []
        for (samples, *_), (loss, lr, scale) in zip(buffered, scalars):
            events.extend([
                ("Train/Samples/train_loss", float(loss), samples),
                ("Train/Samples/lr", float(lr), samples),
                ("Train/Samples/loss_scale", float(scale), samples),
            ])
        self.monitor.write_scalars(events)

    def set_flops_per_batch(self, flops: float) -> None:
        """Analytic per-batch flops override for the profiler. XLA's
        cost analysis counts a lax.scan body once, so scan-over-layers
        models (our GPT) undercount; pass e.g.
        ``gpt.train_flops_per_token(cfg, S) * tokens_per_batch``."""
        self._flops_per_batch = flops

    def _run_flops_profile(self, batch: PyTree) -> None:
        """One-step flops profile (ref: engine.py:1535-1540 triggers the
        FlopsProfiler for flops_profiler.profile_step). Static XLA cost
        analysis of the already-compiled train step + this step's
        measured wall time → achieved TFLOPS / MFU."""
        from deepspeed_tpu.profiling.flops_profiler import (
            analyze_compiled, device_peak_flops)
        try:
            cost = analyze_compiled(self._train_step, self.state, batch)
        except Exception as e:  # pragma: no cover - backend-dependent
            log_dist(f"flops profile unavailable: {e}", ranks=[0])
            return
        override = getattr(self, "_flops_per_batch", None)
        if override:
            cost = dict(cost, flops=float(override))
        dur = max(self._last_step_duration, 1e-9)
        n_params = count_parameters(self.state.params)
        achieved = cost["flops"] / dur
        peak = device_peak_flops()
        n_dev = max(1, len(jax.devices()))
        lines = [
            "", "-" * 64, "DeepSpeed-TPU Flops Profiler (train step)",
            "-" * 64,
            f"profile step:        {self.global_steps + 1}",
            f"params:              {n_params / 1e6:.2f} M",
            f"step flops:          {cost['flops'] / 1e12:.3f} TF",
            f"HBM bytes accessed:  {cost['bytes_accessed'] / 1e9:.2f} GB",
            f"step latency:        {dur * 1e3:.2f} ms",
            f"achieved throughput: {achieved / 1e12:.2f} TFLOPS "
            f"({achieved / n_dev / 1e12:.2f}/device)",
            f"samples/sec:         {self.config.train_batch_size / dur:.1f}",
        ]
        if peak:
            lines.append(
                f"MFU:                 {achieved / (peak * n_dev) * 100:.1f}%")
        lines.append("-" * 64)
        log_dist("\n".join(lines), ranks=[0])
        out = self.config.flops_profiler.output_file
        if out:
            with open(out, "w") as f:
                f.write("\n".join(lines) + "\n")

    # batch-dict keys whose axis 1 is a sequence dimension; other leaves
    # (class labels, masks with sequence elsewhere, ...) are left alone
    CURRICULUM_SEQ_KEYS = ("tokens", "input_ids", "targets", "labels",
                           "loss_mask", "attention_mask", "position_ids")

    def set_curriculum_transform(self, fn) -> None:
        """Override the seqlen truncation with a custom
        ``fn(batch, difficulty) -> batch`` (required for non-dict
        batches or models whose sequence axis is not axis 1)."""
        self._curriculum_transform = fn

    def _apply_curriculum(self, batch: PyTree, difficulty: int) -> PyTree:
        """seqlen curriculum: truncate the sequence axis (axis 1) of the
        well-known token/label keys of a dict batch. Each distinct
        difficulty is one XLA program — difficulty_step bounds the
        recompile count (ref: the fwd-kwarg seqlen injection,
        engine.py:1548-1554)."""
        custom = getattr(self, "_curriculum_transform", None)
        if custom is not None:
            return custom(batch, difficulty)
        if self.config.curriculum.curriculum_type != "seqlen":
            return batch
        if not isinstance(batch, dict):
            raise TypeError(
                "seqlen curriculum needs a dict batch with token keys "
                f"{self.CURRICULUM_SEQ_KEYS}; for other batch layouts "
                "call engine.set_curriculum_transform(fn)")

        def trunc(x):
            if hasattr(x, "ndim") and x.ndim >= 2 and x.shape[1] > difficulty:
                return x[:, :difficulty]
            return x

        return {k: (trunc(v) if k in self.CURRICULUM_SEQ_KEYS else v)
                for k, v in batch.items()}

    def _take_quantize_step(self, batch, overflow: bool) -> None:
        """Post-step MoQ hook: optionally refresh block eigenvalues at a
        GAS boundary, advance the bit schedule, and recompile the train
        step when a precision switch happened (ref: engine.py:1789-1800;
        the quantization itself runs inside the jitted step, see
        _build_train_step)."""
        if self.eigenvalue is not None and self.global_steps % \
                self.eigenvalue.gas_boundary_resolution == 0 and \
                self.quantizer.any_precision_switch():
            # one micro-batch only: the HVP costs ~2x a backward pass and
            # must fit in the same HBM the gas-split train step fits in
            micro_bs = self.config.train_micro_batch_size_per_gpu * \
                self.dp_world_size

            def slice_leaf(x):
                # only array leaves with a leading batch axis can be
                # micro-sliced; scalars/rank-0 leaves (and non-addressable
                # multi-host shards, which cannot be indexed host-side)
                # pass through unchanged
                if not hasattr(x, "ndim") or x.ndim < 1 or \
                        x.shape[0] < micro_bs:
                    return x
                if isinstance(x, jax.Array) and not x.is_fully_addressable:
                    return x
                return x[:micro_bs]

            micro = jax.tree_util.tree_map(slice_leaf, batch)
            self.block_eigenvalue = self.eigenvalue.compute_eigenvalue(
                self._eigenvalue_loss, self.state.params, micro,
                self.state.rng)
        switched = self.quantizer.advance(
            overflow=overflow,
            eigenvalue_enabled=self.eigenvalue is not None,
            block_eigenvalue=self.block_eigenvalue)
        if switched:
            if self.offload_enabled:
                self._grad_step = self._build_grad_step()
            else:
                self._train_step = self._build_train_step(self._donate_state)

    def destroy(self) -> None:
        """Flush and release engine-owned sinks (monitor/TB writer) and
        any pending delayed param update + its worker thread."""
        self.flush_delayed_update()
        if getattr(self, "_dpu_executor", None) is not None:
            self._dpu_executor.shutdown(wait=True)
        self._flush_monitor_buffer()
        self.monitor.close()

    # familiarity wrappers --------------------------------------------
    def __call__(self, batch):
        return self.forward(batch)

    def forward(self, batch, rng: Optional[jax.Array] = None):
        """Inference/eval forward (loss only; ref: engine.py:1523)."""
        self.flush_delayed_update()
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        loss, _ = self._eval_step(self.state.params, self._shard_batch(batch), rng)
        return loss

    def eval_batch(self, batch, rng: Optional[jax.Array] = None):
        self.flush_delayed_update()
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        return self._eval_step(self.state.params, self._shard_batch(batch), rng)

    def backward(self, loss):  # pragma: no cover - API parity shim
        raise RuntimeError(
            "On TPU the forward/backward/step triple is fused into "
            "engine.train_batch(batch); call that instead "
            "(see SURVEY.md §3.2 for the mapping).")

    def step(self):  # pragma: no cover - API parity shim
        raise RuntimeError("see DeepSpeedEngine.backward — use train_batch().")

    # properties ------------------------------------------------------
    @property
    def params(self):
        self.flush_delayed_update()
        return self.state.params

    @property
    def train_batch_size(self):
        return self.config.train_batch_size

    @property
    def train_micro_batch_size_per_gpu(self):
        return self.config.train_micro_batch_size_per_gpu

    @property
    def gradient_accumulation_steps(self):
        return self.config.gradient_accumulation_steps

    @property
    def zero_optimization_stage(self):
        return self.config.zero.stage

    def zero_optimization(self):
        return self.config.zero.enabled

    def get_global_grad_norm(self):
        return None  # available in train metrics

    def get_lr(self):
        return [float(self.lr_schedule(int(self.state.step)))]

    def get_loss_scale(self):
        return float(self.state.scale_state.loss_scale)

    def _report_progress(self, metrics):
        lr = float(metrics["lr"])
        loss = float(metrics["loss"])
        log_dist(
            f"step={self.global_steps}, skipped={self.skipped_steps}, "
            f"lr={lr:.3e}, loss={loss:.4f}, "
            f"loss_scale={float(metrics['loss_scale']):.1f}", ranks=[0])

    # checkpointing ---------------------------------------------------
    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[Dict] = None,
                        save_latest: bool = True):
        self.flush_delayed_update()
        from deepspeed_tpu.runtime.checkpointing import save_checkpoint
        return save_checkpoint(self, save_dir, tag=tag,
                               client_state=client_state or {},
                               save_latest=save_latest)

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_optimizer_states: bool = True,
                        load_lr_scheduler_states: bool = True,
                        strict: bool = False):
        # join-and-DISCARD any in-flight DPU update: the worker must not
        # mutate host masters during restore, and its pre-load result
        # must never overwrite the restored weights
        if getattr(self, "_dpu_pending", None) is not None:
            self._dpu_pending.result()
            self._dpu_pending = None
        from deepspeed_tpu.runtime.checkpointing import load_checkpoint
        return load_checkpoint(self, load_dir, tag=tag,
                               load_optimizer_states=load_optimizer_states,
                               strict=strict)

    def consolidated_16bit_state_dict(self):
        """Gather full (unsharded) compute-dtype params on host
        (ref: engine.py:3060 _zero3_consolidated_16bit_state_dict)."""
        # the gather-and-cast program is cached on the engine: a fresh
        # jit(lambda) per call would recompile every checkpoint save
        # (dslint DS002)
        fn = getattr(self, "_consolidate_16bit_fn", None)
        if fn is None:
            def _gather_cast(p):
                return _cast_tree(p, self.compute_dtype)
            fn = jax.jit(_gather_cast,
                         out_shardings=jax.tree_util.tree_map(
                             lambda _: NamedSharding(self.mesh, P()),
                             self.state.params))
            self._consolidate_16bit_fn = fn
        return jax.device_get(fn(self.state.params))

    def module_state_dict(self):
        """The param pytree (the reference's module.state_dict analog,
        ref: engine.py:3107)."""
        return self.state.params

    def save_16bit_model(self, save_dir: str,
                         save_filename: str = "model_weights.npz") -> bool:
        """Consolidate the (possibly ZeRO-3-sharded) weights and save ONE
        flat compute-dtype npz (ref: engine.py:3136 save_16bit_model —
        there a torch .bin; here a numpy archive with path-joined keys;
        bf16 leaves are stored as uint16 bit patterns with a dtype
        manifest since npz has no bf16). Load with
        ``runtime.checkpointing.load_16bit_model``."""
        self.flush_delayed_update()
        from deepspeed_tpu.runtime.checkpointing import write_16bit_model
        write_16bit_model(self.consolidated_16bit_state_dict(),
                          save_dir, save_filename)
        return True
