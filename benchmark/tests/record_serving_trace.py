"""Records the small SERVING trace the provenance readers are tested on:
a 2-layer model through `ServingEngine` with telemetry on, a few steps.
Run it on the chip (`chiprun -- python3
benchmark/tests/record_serving_trace.py`); it writes
`chiprun_out/small_serve.xplane.pb`, the program's own provenance table
(`small_serve.provenance.json`, from `cost_registry.to_json()`) and the
facts the test pins (`small_serve.json`): how many steps, dispatches and
tokens the traced window held, by the program's own ring. Not a test
itself."""

import glob
import json
import os
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

SLOTS, BLOCK, BLOCKS, CHUNK = 4, 16, 48, 64
PROMPTS = ((70, 6), (40, 5), (100, 4))      # (prompt, answer) tokens


def main():
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ServeRequest, ServingEngine
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.telemetry import Telemetry

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit("record_serving_trace: needs a TPU")
    cfg = gpt.GPTConfig(vocab_size=512, n_layers=2, n_heads=4, d_model=256,
                        max_seq_len=256, dtype=jnp.bfloat16)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    eng = deepspeed_tpu.init_inference((cfg, params), dtype=jnp.bfloat16)
    tel = Telemetry()
    srv = ServingEngine(eng, num_slots=SLOTS, block_size=BLOCK,
                        num_blocks=BLOCKS, prefill_chunk=CHUNK,
                        telemetry=tel)
    rng = np.random.default_rng(0)

    def requests(tag):
        return [ServeRequest(rid=f"{tag}{i}", max_new_tokens=a,
                             prompt=rng.integers(1, 512, p).astype(np.int32))
                for i, (p, a) in enumerate(PROMPTS)]

    srv.run(requests("warm"))               # compiles both programs
    tel.tracer.reset()

    out = os.path.join(ROOT, ".bench_out", "record_serve")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench_traced_window"):
        srv.run(requests("r"))
    jax.profiler.stop_trace()

    src = sorted(glob.glob(os.path.join(out, "plugins", "profile", "*",
                                        "*.xplane.pb")))[-1]
    dst_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(dst_dir, exist_ok=True)
    dst = os.path.join(dst_dir, "small_serve.xplane.pb")
    shutil.copy(src, dst)
    with open(os.path.join(dst_dir, "small_serve.provenance.json"),
              "w") as f:
        json.dump(srv.cost_registry.to_json(), f)
    spans = tel.tracer.spans()

    def count(name):
        return sum(r[1] == name for r in spans)

    facts = {
        "device_kind": devs[0].device_kind, "bytes": os.path.getsize(dst),
        "decode_impl": srv.decode_impl, "layers": cfg.n_layers,
        "pool_blocks": srv.cache.num_blocks - 1, "block_size": BLOCK,
        "kv_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
        "steps": count("serve.step"),
        "prefill_dispatches": count("serve.prefill"),
        "decode_dispatches": sum(r[1] == "serve.decode" and r[5]["live"] > 0
                                 for r in spans),
        "dispatches": count("serve.dispatch"),
        "tokens": sum(r[5]["tokens"] for r in spans
                      if r[1] == "serve.emit"),
        "ring_enqueue_ms_median": float(np.median(
            [(r[6] - r[0]) * 1e3 for r in spans
             if r[1] == "serve.dispatch.enqueue"])),
    }
    with open(os.path.join(dst_dir, "small_serve.json"), "w") as f:
        json.dump(facts, f, indent=1)
    print(json.dumps(facts))


if __name__ == "__main__":
    main()
