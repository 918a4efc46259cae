"""Records the small trace the reduction is tested on. Run it on the chip
(`chiprun [--chips 4] -- python3 benchmark/tests/record_trace.py`); it writes
`chiprun_out/small_<n>chip.xplane.pb` and, beside it, the facts the test pins
(`.json`): how many calls were made inside which spans. Not a test itself.

The program is deliberately plain: a scanned stack of matmuls (a `while`
with nested body events), a `psum` over the chips when there are several,
and two named host spans around the calls."""

import glob
import json
import os
import shutil
import sys

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CALLS = 3


def main():
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")
    n = len(devs)
    mesh = Mesh(devs, ("x",))
    rows = NamedSharding(mesh, P("x"))

    def body(x, w):
        y = jnp.tanh(x @ w)
        if n > 1:
            # every chip needs every chip's rows: an all-gather per layer
            y = jax.lax.with_sharding_constraint(y, NamedSharding(mesh, P()))
            y = jax.lax.with_sharding_constraint(y * 0.5, rows)
        return y, None

    @jax.jit
    def step(x, ws):
        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()

    x = jax.device_put(jnp.ones((1024 * n, 2048), jnp.bfloat16), rows)
    ws = jax.device_put(jnp.ones((6, 2048, 2048), jnp.bfloat16) * 0.01,
                        NamedSharding(mesh, P()))
    step(x, ws).block_until_ready()

    out = os.path.join(ROOT, ".bench_out", "record")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench_traced_window"):
        for i in range(CALLS):
            with jax.profiler.TraceAnnotation("step"):
                with jax.profiler.TraceAnnotation("decode_dispatch"):
                    step(x, ws).block_until_ready()
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(out, "plugins", "profile", "*",
                                        "*.xplane.pb")))[-1]
    dst_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(dst_dir, exist_ok=True)
    dst = os.path.join(dst_dir, f"small_{n}chip.xplane.pb")
    shutil.copy(src, dst)
    with open(dst.replace(".xplane.pb", ".json"), "w") as f:
        json.dump({"chips": n, "calls": CALLS, "layers": 6,
                   "bytes": os.path.getsize(dst),
                   "device_kind": devs[0].device_kind}, f)
    print(dst, os.path.getsize(dst))


if __name__ == "__main__":
    main()
