"""Adaptive micro-batched serving: per-frame stream in, per-frame labels
out, with the chip seeing full batches.

tensor_batch groups whatever frames are queued (up to --batch) within a
--budget-ms latency window — ONE H2D transfer + ONE invoke per group —
and tensor_unbatch restores the per-frame stream, PTS intact. Under load
this converges to full batches (~3x streaming FPS vs the per-frame
pipeline on the round-5 v5e, whose dispatches paid a ~70 ms round trip);
an idle stream pays at most the budget in latency.

    python examples/adaptive_batch_serving.py [--frames 400] [--batch 16]
"""

import _bootstrap  # noqa: F401  (repo-root import shim for source checkouts)

import argparse
import sys
import tempfile
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=400)
    ap.add_argument("--size", type=int, default=224)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--budget-ms", type=float, default=50.0)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")

    from nnstreamer_tpu.graph import Pipeline

    with tempfile.NamedTemporaryFile("w", suffix=".txt", delete=False) as f:
        f.write("\n".join(f"class{i}" for i in range(1001)))
        labels = f.name

    p = Pipeline()
    src = p.add_new("videotestsrc", width=args.size, height=args.size,
                    pattern="random", num_buffers=args.frames)
    conv = p.add_new("tensor_converter")
    bat = p.add_new("tensor_batch", max_batch=args.batch,
                    budget_ms=args.budget_ms)
    filt = p.add_new("tensor_filter", framework="xla-tpu",
                     model=f"zoo://mobilenet_v2?size={args.size}"
                           f"&batch={args.batch}")
    unb = p.add_new("tensor_unbatch")
    dec = p.add_new("tensor_decoder", mode="image_labeling", option1=labels,
                    async_depth=64)
    arrivals = []
    sink = p.add_new("tensor_sink",
                     new_data=lambda b: arrivals.append(time.monotonic()))
    Pipeline.link(src, conv, bat, filt, unb, dec, sink)
    t0 = time.monotonic()
    p.run(timeout=600)
    wall = time.monotonic() - t0
    print(f"{len(arrivals)} per-frame results in {wall:.2f}s "
          f"({len(arrivals) / wall:.1f} FPS end-to-end, "
          f"batch={args.batch}, budget={args.budget_ms}ms)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
