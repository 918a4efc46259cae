"""Driver for configurations of ``"kind": "train"``: GPT-2 through
``deepspeed_tpu.initialize`` under a ``batches`` traffic mix, on a mesh of
exactly the chips the cell asks for.

The step loop and the arithmetic are ``bench.py``'s: every step is fully
synced (``float(loss)``), the rate comes from the median step.

Correctness has two parts. Before the engine takes the weights, the
program's own forward (``gpt.forward`` under the cell's ``GPTConfig``: the
flash kernel, bf16) runs on a few sample sequences and its logits are held
to the plain reference's at every position (``forward_check``). Then the
first (compiling) step of the ENGINE runs on those sequences tiled to the
global batch, so its loss equals the reference's mean loss on them; the
measured steps run on the seeded batch, and somewhere in the window its loss
on that batch has to lie below where it began (``loss_descends``: what a
state returned unchanged cannot show)."""
# (bench.py reports the median step; here the end-to-end rate is taken over
# all the steps and all the time of the window, which the contract asks of a
# rate, and the median step is the per-layer metric `train_step_ms`.)

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import spans as spans_lib
from harness import weights
from harness.stats import median

SAMPLE_SEQUENCES = 2
# The program's logits (bf16 weights and activations, 48 layers of bf16
# residual adds, bf16 logits) against the float32 reference on the same bf16
# weights, at every one of 2 x 1,024 positions x 50,257 logits. Random GPT-2
# weights give logits of standard deviation ~0.8 and magnitude up to ~4.5.
# Measured on the chip (PERF.md section 4): largest error 0.072-0.074,
# root-mean-square 0.0121-0.0125. The tolerances are 2.4 to 3.4 times that.
# Weights rounded to fp8 (benchmark/tools/train_check_sensitivity.py) read
# 2.50 and 0.44 on the chip, ten times over either.
LOGIT_TOL_MAX_ABS = 0.25
LOGIT_TOL_RMS = 0.03
# Per-token loss: the error of one log-probability, same origin as above
# (measured 0.038-0.050 over 3 seeds; fp8 weights 1.59).
TOKEN_LOSS_TOL_ABS = 0.15
# The engine's first-step loss against the reference's mean loss on the
# sample: a mean over 2,048 tokens of per-token errors with mixed signs.
# Largest of 25 chip runs over 10 seeds: 3.3e-4 (PERF.md). Six times that.
LOSS_TOL_ABS = 2e-3


def loss_descends(losses):
    """What a run can show of the optimizer without a reference that steps
    beside it: ``losses[0]`` is the loss on the measured batch before any
    step on it, ``losses[k]`` the loss on the same batch after ``k``
    optimizer steps on it, and the lowest of those has to lie below the
    first. Returns (verdict, lowest loss after a step minus the first).

    The two readings the limit stands between (PERF.md section 6, PR 56): a
    state returned unchanged reads exactly 0 (no dropout: the same state
    gives the same loss), sound runs read -0.52 or lower after eight steps.
    It does not hold a single step or the window's last loss: on a sound
    program the first step on the batch rose on one seed of three and the
    twentieth spiked on another, and nothing here can tell that from a
    fault (no reference follows the steps: PERF.md section 7). Both numbers
    stay in the output (``loss_after_first_step_minus_before``,
    ``last_loss_minus_first``), held to nothing."""
    later = losses[1:]
    if not later:
        return False, float("nan")
    fall = min(later) - losses[0]
    return bool(fall < 0), fall


def forward_check(params, sample, cfg, n_head, reference,
                  reference_params=None):
    """The program's forward on ``sample`` [B, S + 1] against the plain
    reference's, position by position. Returns (ok, detail, reference's
    mean loss). Runs on one device, before any engine or mesh exists.
    ``reference_params``: the weights the reference sees, where they differ
    from the program's (``tools/train_check_sensitivity.py``)."""
    from deepspeed_tpu.models import gpt
    tokens, targets = jnp.asarray(sample[:, :-1]), jnp.asarray(sample[:, 1:])
    ref = reference.logits(params if reference_params is None
                           else reference_params, tokens, n_head)
    got = jax.jit(lambda p, t: gpt.forward(p, t, cfg))(params, tokens)

    # the targets are an operand, not a constant of the program: a
    # constant would make every seed a program of its own, compiled by the
    # first run of that seed and loaded by the second (PERF.md 7 x)
    @jax.jit
    def compare(ref, got, targets):
        got = got.astype(jnp.float32)
        err = jnp.abs(got - ref)
        ref_l = reference.token_losses(ref, targets)
        got_l = reference.token_losses(got, targets)
        return {"max_abs_logit_error": err.max(),
                "rms_logit_error": jnp.sqrt((err ** 2).mean()),
                "rms_reference_logit": jnp.sqrt((ref ** 2).mean()),
                "largest_reference_logit": jnp.abs(ref).max(),
                "argmax_agreement_with_reference":
                    (got.argmax(-1) == ref.argmax(-1)).mean(),
                "max_abs_token_loss_error": jnp.abs(got_l - ref_l).max(),
                "reference_loss": ref_l.mean(),
                "program_forward_loss": got_l.mean()}
    d = {k: float(v) for k, v in compare(ref, got, targets).items()}
    d["positions_compared"] = int(tokens.size)
    ok = (d["max_abs_logit_error"] < LOGIT_TOL_MAX_ABS
          and d["rms_logit_error"] < LOGIT_TOL_RMS
          and d["max_abs_token_loss_error"] < TOKEN_LOSS_TOL_ABS)
    d.update(tolerance_max_abs=LOGIT_TOL_MAX_ABS, tolerance_rms=LOGIT_TOL_RMS,
             tolerance_token_loss=TOKEN_LOSS_TOL_ABS, ok=bool(ok))
    return bool(ok), d, d["reference_loss"]


def run(ctx):
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.parallel import mesh as mesh_lib

    cell, say = ctx.cell, ctx.say
    hp, tr, mix = cell.config["model"], cell.config["training"], cell.traffic
    clock = time.perf_counter
    rng = np.random.default_rng(ctx.seed)
    batch, seq = int(mix["global_batch"]), int(mix["seq"])
    setup = {}

    t = clock()
    params = weights.gpt2_params(ctx.seed, hp, jnp.bfloat16)
    jax.block_until_ready(params)
    setup["weights_s"] = clock() - t

    vocab = int(hp["vocab_size"])
    tokens = rng.integers(0, vocab, (batch, seq + 1)).astype(np.int32)
    sample = tokens[:SAMPLE_SEQUENCES]
    tiled = np.tile(sample, (batch // SAMPLE_SEQUENCES, 1))

    cfg = gpt.GPTConfig(
        vocab_size=vocab, n_layers=int(hp["n_layer"]),
        n_heads=int(hp["n_head"]), d_model=int(hp["n_embd"]),
        max_seq_len=seq, dtype=jnp.bfloat16, remat=True,
        dropout=float(tr["dropout"]),
        remat_policy=tr["remat_policy"],
        flash_block_q=int(tr["flash_block"]),
        flash_block_kv=int(tr["flash_block"]),
        loss_chunk=int(tr["loss_chunk"]))

    # ---- the program's forward against the plain reference on the sample,
    # before the engine takes (and donates) the weights
    t = clock()
    forward_ok, forward_detail, ref_loss = forward_check(
        params, sample, cfg, int(hp["n_head"]), cell.reference())
    setup["reference_check_s"] = clock() - t

    # ---- the engine ----------------------------------------------------------
    t = clock()
    fsdp = int(tr["mesh"]["fsdp"])
    if fsdp != cell.chips:
        raise SystemExit(f"train: configuration {cell.entry['config']} shards "
                         f"over fsdp={fsdp}, the cell asks for {cell.chips} "
                         f"chips")
    devices = jax.devices()[:fsdp]
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(data=1, fsdp=fsdp), devices)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=gpt.make_loss_fn(cfg), model_parameters=params, mesh=mesh,
        config={
            "train_batch_size": batch,
            "bf16": {"enabled": True,
                     "memory_efficient": bool(tr["bf16_memory_efficient"])},
            "zero_optimization": {"stage": int(tr["zero_stage"])},
            "optimizer": {"type": "adamw",
                          "params": {"lr": float(tr["lr"]),
                                     "weight_decay": float(tr["weight_decay"])}},
            "steps_per_print": 10_000_000,
        })
    del params
    setup["engine_s"] = clock() - t
    say(info="train_engine", attention=gpt.attention_impl(cfg, seq),
        mesh=dict(zip(engine.mesh.axis_names,
                      [int(x) for x in engine.mesh.devices.shape])))

    # ---- first step: compiles (or loads the cache), and is checked ------------
    t = clock()
    first_loss = float(engine.train_batch({"tokens": tiled})["loss"])
    setup["first_step_s"] = clock() - t
    loss_err = abs(first_loss - ref_loss)
    data = {"tokens": tokens}
    # one unmeasured step on the measured batch: the window opens on a
    # program and inputs that have already run once
    t = clock()
    losses = [float(engine.train_batch(data)["loss"])]
    setup["warm_step_s"] = clock() - t

    # ---- window ------------------------------------------------------------------
    log = spans_lib.SpanLog()
    gc.collect()
    gc.freeze()
    ws = clock()
    we = ws + ctx.seconds
    compiles_before = ctx.compiles.count
    tracing = None
    t_end = ws
    # every step lies inside the window: a step starts only if the median
    # so far says it will end inside it
    durations = []
    while True:
        est = median(durations) if durations else setup["warm_step_s"]
        if clock() + est > we:
            break
        if ctx.trace and tracing is None \
                and clock() + (ctx.trace_steps + 0.5) * est > we:
            tracing = ctx.start_trace()
        with log.span("train_step"):
            losses.append(float(engine.train_batch(data)["loss"]))
        t_end = clock()
        durations.append(log.spans[-1][2] - log.spans[-1][1])
    if tracing is not None:
        ctx.stop_trace(tracing)
    compiled_inside = ctx.compiles.count - compiles_before
    gc.unfreeze()

    h1 = tracing["t_start"] if tracing is not None else t_end
    steps = [s[2] - s[1] for s in log.named("train_step", ws, h1)]
    n_steps = len(durations)
    step_s = median(steps)
    finite = [bool(np.isfinite(x)) for x in losses]
    descends, fall = loss_descends(losses)
    first_step = losses[1] - losses[0] if len(losses) > 1 else float("nan")
    # the rate is all the window's work over all its time; the median step
    # stands beside it as the per-layer `train_step_ms`
    e2e = {"train_tok_s_chip":
           n_steps * batch * seq / (t_end - ws) / cell.chips}
    mfu = e2e["train_tok_s_chip"] * ctx.rooflines.gpt2_train_flops_per_token(
        hp, seq) / ctx.peaks["bf16_flops"]
    say(info="window", seconds=t_end - ws, steps=n_steps,
        step_ms_median=step_s * 1e3, step_ms_min=min(steps) * 1e3,
        step_ms_max=max(steps) * 1e3, compiles_inside=compiled_inside,
        train_mfu_pct=100.0 * mfu, losses_first_last=[losses[0], losses[-1]],
        losses=losses)
    say(info="correctness", forward=forward_detail,
        reference_loss_on_sample=ref_loss,
        first_step_loss=first_loss, abs_error=loss_err,
        tolerance=LOSS_TOL_ABS, all_finite=all(finite),
        lowest_loss_in_window_minus_before=fall,
        loss_after_first_step_minus_before=first_step,
        last_loss_minus_first=losses[-1] - losses[0])
    correct = (forward_ok and loss_err < LOSS_TOL_ABS and all(finite)
               and descends and compiled_inside == 0)
    run = {"kind": "train", "log": log, "host_window": (ws, h1),
           "window": (ws, t_end), "batch": batch, "seq": seq,
           "chips": cell.chips, "heads": int(hp["n_head"]),
           "head_dim": int(hp["n_embd"]) // int(hp["n_head"]),
           "layers": int(hp["n_layer"]), "devices": devices,
           "step_ms_median": step_s * 1e3}
    return {"correct": bool(correct), "attempted": n_steps,
            "failed": int(sum(not f for f in finite)),
            "end_to_end": e2e, "setup_items": setup, "window_start": ws,
            # every number compared, beside its limit (run.py prints them)
            "compared": [
                ("forward_max_abs_logit_error",
                 forward_detail["max_abs_logit_error"], LOGIT_TOL_MAX_ABS),
                ("forward_rms_logit_error",
                 forward_detail["rms_logit_error"], LOGIT_TOL_RMS),
                ("forward_max_abs_token_loss_error",
                 forward_detail["max_abs_token_loss_error"],
                 TOKEN_LOSS_TOL_ABS),
                ("first_step_loss_abs_error", loss_err, LOSS_TOL_ABS),
                ("non_finite_losses", int(sum(not f for f in finite)), 0),
                ("lowest_loss_in_window_minus_before", fall, "<0"),
                # numbers, not verdicts
                ("loss_after_first_step_minus_before", first_step,
                 "not held"),
                ("last_loss_minus_first", losses[-1] - losses[0],
                 "not held"),
                ("compiles_inside_window", compiled_inside, 0)],
            "run": run}
