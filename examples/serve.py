#!/usr/bin/env python
"""Serving-entry demo — the inference sibling of examples/train.py.

Loads a tiny random-weight causal decoder, submits a few token-id
prompts, and streams greedy completions from the continuous-batching
engine (there is no tokenizer in this framework — prompts and outputs
are vocabulary ids, which is all the serving stack deals in). The
engine defaults to the PAGED KV cache (block pool + copy-on-write
prefix reuse + chunked prefill — docs/serving.md); ``--dense`` is the
one-flag escape hatch back to the PR-1 slot-dense cache.

Usage:
    JAX_PLATFORMS=cpu python examples/serve.py
    python examples/serve.py --prompts 5 --max-new 24 --temperature 0.8
    python examples/serve.py --dense   # slot-dense fallback
"""

import argparse
import random
import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--prompts", type=int, default=3,
                    help="number of random prompts to submit")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=2,
                    help="decode-batch slots (fewer than prompts shows "
                         "queueing + slot reuse)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dense", action="store_true",
                    help="escape hatch: the PR-1 slot-dense KV cache "
                         "instead of the default paged block pool")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block (paged cache)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prefill chunk size (paged cache)")
    args = ap.parse_args(argv)

    from distributed_tensorflow_tpu import serve
    from distributed_tensorflow_tpu.models import transformer as tfm
    from distributed_tensorflow_tpu.parallel import cluster

    cluster.configure_compile_cache()
    cfg = tfm.TransformerConfig(
        vocab_size=256, max_len=128, num_layers=2, d_model=64, num_heads=4,
        d_ff=128, dropout=0.0, dtype="float32", causal=True, pre_ln=True,
    )
    eng = serve.ServeEngine.with_random_params(
        cfg, seed=args.seed, num_slots=args.slots,
        temperature=args.temperature, top_k=args.top_k,
        paged=not args.dense, block_size=args.block_size,
        prefill_chunk=args.prefill_chunk,
    )

    rng = random.Random(args.seed)
    prompts = [
        [rng.randrange(cfg.vocab_size) for _ in range(rng.randint(3, 10))]
        for _ in range(args.prompts)
    ]
    uids = {
        eng.submit(p, max_new_tokens=args.max_new): p for p in prompts
    }
    print(f"submitted {len(prompts)} prompts into {args.slots} slots\n")

    # drive the engine step by step, streaming tokens as they land
    while eng.sched.has_work:
        stats = eng.step()
        for uid, tok in stats.tokens:
            print(f"  req {uid} += {tok}")
        for uid in stats.finished:
            print(f"  req {uid} done")
    print()
    for req in eng.sched.drain_finished().values():
        print(f"req {req.uid}: prompt={list(req.prompt)}")
        print(f"        -> {req.generated}  ({req.finish_reason})")

    # request-level telemetry the engine recorded along the way
    # (docs/observability.md; scrape-able via obs.serve_http)
    reg = eng.registry
    ms = lambda s: f"{s * 1e3:.1f}ms"  # noqa: E731
    ttft, tpot = reg.get("serve_ttft_seconds"), reg.get("serve_tpot_seconds")
    print(f"\ntelemetry: ttft p50={ms(ttft.percentile(0.5))} "
          f"p99={ms(ttft.percentile(0.99))}  "
          f"tpot p50={ms(tpot.percentile(0.5))}  "
          f"tokens={int(reg.get('serve_tokens_total').value)}")
    if not args.dense:
        # the paged cache's own surface (docs/serving.md "Paged KV")
        print(f"paged kv: block_size={args.block_size} "
              f"pool={eng.cache.num_blocks} blocks  "
              f"reuse_hits={int(reg.get('prefix_reuse_hits_total').value)}  "
              f"prefill_chunks={int(reg.get('prefill_chunks_total').value)}  "
              f"cow_copies={eng.alloc.cow_copies}")
        eng.drain()
        assert eng.alloc.blocks_free == eng.cache.num_blocks, \
            "block leak at shutdown"


if __name__ == "__main__":
    main()
