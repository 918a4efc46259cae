"""Times the held experts' grouped product on the chip at the K-EXAONE
cell's shapes, for the choice of ``moe/expert_share.py``'s implementation
and tiling (PERF.md, PR 28): a decode step (48 tokens) and a prefill chunk
(512 tokens), 8 of 128 experts a token, 16 held, bf16; ``ragged_dot``
against the Mosaic grouped matmul at several tilings, and a plain read of
the 16 experts' weights as the floor. One JSON row each.

    chiprun -- python3 benchmark/tools/moe_grouped_bench.py"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from deepspeed_tpu.moe import expert_share  # noqa: E402

D, F, E, K, HELD = 6144, 2048, 128, 8, 16


def timed(fn, *a, n=20):
    out = fn(*a)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def main():
    key = jax.random.key(0, impl="rbg")
    ks = jax.random.split(key, 5)
    experts = {n: {"kernel": (jax.random.normal(k, (HELD,) + s, jnp.float32)
                              * 0.02).astype(jnp.bfloat16)}
               for n, k, s in (("wg", ks[0], (D, F)), ("wi", ks[1], (D, F)),
                               ("wo", ks[2], (F, D)))}
    floor = jax.jit(lambda e: sum(jnp.sum(v["kernel"].astype(jnp.float32))
                                  for v in e.values()))
    print(json.dumps({"what": "read 16 experts' weights once",
                      "ms": timed(floor, experts),
                      "least_ms_at_819GBps": HELD * 3 * D * F * 2 / 819e9
                      * 1e3}), flush=True)
    rng = np.random.default_rng(0)
    for T in (48, 512):
        h = (jax.random.normal(ks[3], (T, D), jnp.float32)).astype(
            jnp.bfloat16)
        sel = jnp.asarray(np.stack([rng.choice(E, K, replace=False)
                                    for _ in range(T)]), jnp.int32)
        w = jnp.ones((T, K), jnp.float32) / K
        want = None
        for impl, tiling in [("ragged_dot", None),
                             ("gmm", (128, 512, 512)),
                             ("gmm", (128, 1024, 1024)),
                             ("gmm", (128, 2048, 1024)),
                             ("gmm", (128, 1024, 2048)),
                             ("gmm", (128, 2048, 2048)),
                             ("gmm", (256, 1024, 1024)),
                             ("gmm", (128, 6144, 512))]:
            if tiling:
                expert_share.GMM_TILING = tiling
            fn = jax.jit(lambda h, e, sel, w, impl=impl:
                         expert_share.held_experts_ffn(
                             h, e, sel, w, (0, HELD), impl))
            try:
                ms = timed(fn, h, experts, sel, w)
                out, stats = fn(h, experts, sel, w)
                out = np.asarray(out, np.float32)
                if want is None:
                    want = out
                row = {"tokens": T, "impl": impl, "tiling": tiling,
                       "ms": ms, "pairs_held": int(stats[0]),
                       "experts_touched": int(stats[3]),
                       "max_abs_diff_vs_ragged_dot": float(
                           np.abs(out - want).max()),
                       "max_abs": float(np.abs(want).max())}
            except Exception as e:  # a tiling the compiler refuses
                row = {"tokens": T, "impl": impl, "tiling": tiling,
                       "error": str(e)[:300]}
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
