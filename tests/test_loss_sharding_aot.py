"""Under ZeRO-3 the chunked loss scans each shard's own tokens on a
projection gathered once, read off the compiled train step.

ZeRO-3 cuts the tied ``wte`` along the hidden dimension when the
vocabulary does not divide by the shards, and that is the dimension the
projection contracts. A chunk scan over the GLOBAL token axis then
all-reduces every chunk's ``f32[chunk, vocabulary]`` logits, forward and
again in the recomputing backward: on four v5e chips 64 all-reduces of
412 MB a step, 16.4% of it (PERF.md, PR 33). The engine's step is compiled
here for 4 virtual CPU devices at a small size with the same shape of
problem (vocabulary 257) and the executable is asked what the mechanism
is: which collectives carry what, and where they sit.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import gpt
from deepspeed_tpu.ops import cross_entropy
from deepspeed_tpu.parallel import mesh as mesh_lib

V, H, SEQ, BATCH, CHUNK = 257, 64, 64, 16, 128   # 256 tokens a shard of 4

_CALLS = re.compile(
    r"\b(?:body|condition|to_apply|calls|true_computation|"
    r"false_computation)=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")


def _cfg():
    return gpt.GPTConfig(vocab_size=V, n_layers=2, n_heads=4, d_model=H,
                         max_seq_len=SEQ, dtype=jnp.bfloat16, remat=True,
                         use_flash_attention=False, loss_chunk=CHUNK)


def _mesh(fsdp):
    return mesh_lib.make_mesh(mesh_lib.MeshSpec(data=1, fsdp=fsdp),
                              jax.devices()[:fsdp])


def _engine(fsdp):
    cfg = _cfg()
    engine, *_ = deepspeed_tpu.initialize(
        model=gpt.make_loss_fn(cfg), mesh=_mesh(fsdp),
        model_parameters=gpt.init_params(jax.random.PRNGKey(0), cfg),
        config={"train_batch_size": BATCH,
                "bf16": {"enabled": True, "memory_efficient": True},
                # the tiny projection is cut like GPT-2 XL's: ZeRO-3 takes
                # the largest dimension that divides, and 257 does not
                "zero_optimization": {"stage": 3,
                                      "stage3_param_persistence_threshold": 0,
                                      "stage3_min_shard_size": 1},
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "steps_per_print": 10 ** 9})
    return engine


def _computations(hlo_text):
    """computation name -> its instruction lines."""
    out, current = {}, None
    for line in hlo_text.splitlines():
        if current is None:
            m = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
            if m and "=" not in line.split("(", 1)[0]:
                current = out.setdefault(m.group(1), [])
        elif line.strip() == "}":
            current = None
        else:
            current.append(line)
    return out


def _inside_loops(computations):
    """Names of the computations that run inside some ``while``: the
    loops' bodies and conditions and everything they call."""
    def callees(line):
        for one, many in _CALLS.findall(line):
            yield from ([one] if one else
                        [n.strip().lstrip("%") for n in many.split(",")])
    todo = [c for lines in computations.values() for line in lines
            if " while(" in line for c in callees(line)]
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in computations:
            continue
        seen.add(name)
        todo += [c for line in computations[name] for c in callees(line)]
    return seen


def _collectives(computations, opcode):
    """(computation, result shapes [(dtype, dims)], line) of every
    ``opcode`` (and its async ``-start``) instruction."""
    found = []
    for comp, lines in computations.items():
        for line in lines:
            m = re.search(rf"= (.*?) {opcode}(?:-start)?\(", line)
            if m:
                shapes = [(d, tuple(int(n) for n in dims.split(",") if n))
                          for d, dims in re.findall(r"(\w+)\[([\d,]*)\]",
                                                    m.group(1))]
                found.append((comp, shapes, line))
    return found


@pytest.fixture(scope="module")
def step_hlo(devices):
    engine = _engine(4)
    wte = engine.state.params["wte"]["embedding"]
    assert wte.sharding.spec == jax.sharding.PartitionSpec(None, "fsdp")
    tokens = np.random.default_rng(0).integers(
        0, V, (BATCH, SEQ + 1)).astype(np.int32)
    batch = engine._shard_batch({"tokens": tokens})
    with jax.set_mesh(engine.mesh):
        compiled = engine._train_step.lower(engine.state, batch).compile()
    return _computations(compiled.as_text())


def test_no_all_reduce_of_a_chunk_of_logits(step_hlo):
    assert any(" while(" in line for lines in step_hlo.values()
               for line in lines), "no loop compiled: the shapes moved"
    logits = [line.strip()[:160]
              for _, shapes, line in _collectives(step_hlo, "all-reduce")
              for _, dims in shapes if V in dims and CHUNK in dims]
    assert not logits, logits


def test_projection_gathered_once_outside_every_loop(step_hlo):
    looped = _inside_loops(step_hlo)
    assert looped
    gathers = _collectives(step_hlo, "all-gather")
    named = [(comp, shapes) for comp, shapes, line in gathers
             if "loss_gather" in line]
    # forward and backward ask for the same gather; the compiler may
    # keep one. Nothing else sits under the scope
    assert 1 <= len(named) <= 2, named
    for comp, shapes in named:
        assert comp not in looped, comp
        assert [dims for _, dims in shapes] == [(V, H)], shapes
    # and no loop gathers the projection under another name
    inside = [line.strip()[:160] for comp, shapes, line in gathers
              if comp in looped and any(V in dims for _, dims in shapes)]
    assert not inside, inside


def test_engine_ready_line_says_what_the_loss_does(devices):
    describe = gpt.make_loss_fn(_cfg()).describe
    with jax.set_mesh(_mesh(1)):
        assert describe()["loss"] == f"chunked({CHUNK})"
    with jax.set_mesh(_mesh(4)):
        assert describe()["loss"] == \
            f"chunked({CHUNK})/shard over fsdp=4, projection gathered"


def test_one_shard_lowers_to_the_unmapped_program(devices):
    """With every batch axis of size 1 the function is called as on a
    single device: the lowered text is the unmapped call's."""
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(BATCH, SEQ, H)), jnp.bfloat16)
    w = jnp.asarray(r.normal(size=(V, H)), jnp.bfloat16)
    t = jnp.asarray(r.integers(0, V, (BATCH, SEQ)), jnp.int32)

    def loss(x, w):
        return cross_entropy.chunked_softmax_xent(x, w, t, chunk=CHUNK)

    def unmapped(x, w):
        return -cross_entropy._xent_ll(x, w, None, t, CHUNK, ()).mean()

    want = jax.jit(jax.value_and_grad(unmapped, (0, 1))).lower(x, w).as_text()
    with jax.set_mesh(_mesh(1)):
        got = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(x, w).as_text()
    assert "shard_map" not in got and "sdy.manual" not in got
    rename = (lambda text: re.sub(r"@(\w+?)_\d+\b", r"@\1", text)
              .replace("jit_unmapped", "jit_loss"))
    assert rename(got) == rename(want)
