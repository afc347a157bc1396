#!/usr/bin/env python
"""Ablation driver for the ResNet-50 bench.

Thin wrapper: each variant is a `bench.py` run with BENCH_* env overrides,
so timing methodology, FLOPs accounting (fwd-only × train multiplier), and
MFU math live in exactly one place — bench.py. One JSON line per variant
to stdout; bench diagnostics pass through on stderr.

Usage: python tools/ablate_resnet.py [variant ...]   (default: all)
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")

# Every variant pins ALL knobs explicitly (never inherits ambient BENCH_*
# from the operator's shell), and the pinned values are echoed into the
# output row, so a sweep can't be silently mislabeled.
_KNOBS = ("BENCH_STEM", "BENCH_NORM_DTYPE", "BENCH_DEBUG_METRICS",
          "BENCH_BATCH", "BENCH_STEPS", "BENCH_BLOCK_IMPL")


def _variant(stem="space_to_depth", norm="bfloat16", dbg="0", batch="256",
             steps="20", blocks="standard"):
    return {"BENCH_STEM": stem, "BENCH_NORM_DTYPE": norm,
            "BENCH_DEBUG_METRICS": dbg, "BENCH_BATCH": batch,
            "BENCH_STEPS": steps, "BENCH_BLOCK_IMPL": blocks}


VARIANTS = {
    "r1_baseline": _variant(stem="conv", norm="float32", dbg="1"),
    "no_metrics": _variant(stem="conv", norm="float32"),
    "bf16_bn": _variant(stem="conv"),
    "s2d_f32bn": _variant(norm="float32"),
    "combo256": _variant(),  # round-2a tuned config, standard blocks
    "combo384": _variant(batch="384"),
    "combo512": _variant(batch="512"),
    "combo1024": _variant(batch="1024"),
    # round-2b fused Pallas conv+BN blocks (ops/fused_conv_bn.py)
    "fused256": _variant(blocks="fused"),
    "fused384": _variant(blocks="fused", batch="384"),
    "fused512": _variant(blocks="fused", batch="512"),
}


def main() -> None:
    names = sys.argv[1:] or list(VARIANTS)
    for name in names:
        env = {k: v for k, v in os.environ.items() if k not in _KNOBS}
        env.update(VARIANTS[name])
        proc = subprocess.run(
            [sys.executable, BENCH], env=env, capture_output=True, text=True
        )
        sys.stderr.write(proc.stderr)
        if proc.returncode != 0:
            print(json.dumps({"variant": name, "error": proc.returncode}),
                  flush=True)
            continue
        result = json.loads(proc.stdout.strip().splitlines()[-1])
        print(json.dumps({"variant": name, **VARIANTS[name], **result}),
              flush=True)


if __name__ == "__main__":
    main()
