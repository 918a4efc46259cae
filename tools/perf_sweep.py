"""Perf sweep for the single-chip GPT training step.

Times the engine's fused train step over a grid of (batch, flash blocks,
remat policy) on the local chip and prints one JSON line per config —
the tuning harness behind bench.py's headline number (analog of the
reference's perf sweep scripts, ref: tests/model/Megatron_GPT2/run_perf*).

Usage: python tools/perf_sweep.py [preset] [steps]
"""

import json
import sys

sys.path.insert(0, ".")

from bench import run_config  # noqa: E402


def main():
    preset = sys.argv[1] if len(sys.argv) > 1 else "gpt2-medium"
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 10
    seq = 1024
    grid = [
        # (batch, flash_block, extra ds-config)
        (8, 512, {}),
        (16, 512, {}),
        (32, 512, {}),
        (16, 256, {}),
        (16, 1024, {}),
        (16, 512, {"bf16": {"enabled": True, "memory_efficient": True}}),
    ]
    for batch, fb, extra in grid:
        overrides = {"zero_optimization": {"stage": 1}}
        overrides.update(extra)
        dt, tps, mfu = run_config(preset, batch, seq, steps,
                                  overrides, flash_block=fb)
        print(json.dumps({
            "preset": preset, "batch": batch, "flash_block": fb,
            "extra": extra,
            "step_ms": round(dt * 1e3, 2),
            "tokens_per_s": round(tps, 1), "mfu": round(mfu, 4)}),
            flush=True)


if __name__ == "__main__":
    main()
