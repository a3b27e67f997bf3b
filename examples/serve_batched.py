"""Batched serving on the paged FP8 KV cache: continuous or bucketed.

Run:  PYTHONPATH=src python examples/serve_batched.py \
          [--arch qwen2-0.5b] [--requests 6] [--slots 3] [--gen 12] \
          [--prompt-lens 4,12,8] [--shared-prefix 16] [--quant fp8_w8kv8] \
          [--scheduler continuous|bucketed] [--cache-impl paged|dense] \
          [--prefix-cache on|off] [--page-size 8] [--pages N] [--chunk 4] \
          [--arrival-rate 0.5] [--mesh 1x2] [--stream]
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import argparse

from repro.launch import serve

EPILOG = """\
schedulers:
  continuous   per-step admission with chunked prefill (long prompts never
               block decode), preemption with page spill/restore when the
               pool runs dry, per-step token streaming.  Default; needs
               --cache-impl paged.
  bucketed     the PR-2 baseline: requests admitted in prompt-length
               buckets, one blocking batched prefill per bucket, worst-case
               page reservation per request.  Works with paged and dense
               caches.

examples:
  # mixed-length Poisson request stream through the continuous scheduler
  python examples/serve_batched.py --requests 8 --slots 3 --gen 12 \\
      --prompt-lens 4,12,20 --arrival-rate 0.5 --stream
  # same stream through the bucketed baseline for comparison
  python examples/serve_batched.py --requests 8 --slots 3 --gen 12 \\
      --prompt-lens 4,12,20 --arrival-rate 0.5 --scheduler bucketed
  # shared-system-prompt stream with ref-counted prefix caching: later
  # requests reuse the shared prompt's KV pages, prefilling only the tail
  python examples/serve_batched.py --requests 8 --slots 3 --gen 12 \\
      --prompt-lens 4,6 --shared-prefix 16 --prefix-cache on \\
      --arrival-rate 0.5
"""


def main():
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        epilog=EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--gen", type=int, default=12)
    ap.add_argument("--prompt-lens", default="8",
                    help="comma list of prompt lengths, cycled over requests")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many shared tokens to every prompt "
                         "(a common system prompt)")
    ap.add_argument("--prefix-cache", default="off", choices=["on", "off"],
                    help="ref-counted prefix caching over the page pool "
                         "(paged pure-GQA caches)")
    ap.add_argument("--policy", default=None,
                    help="named numerics policy preset (default: "
                         "serve_fp8_paged; see "
                         "repro.numerics.available_policies())")
    ap.add_argument("--quant", default=None,
                    help="DEPRECATED alias for --policy (legacy flat "
                         "quant flag, mapped through "
                         "QuantConfig.to_policy())")
    ap.add_argument("--scheduler", default="continuous",
                    choices=["continuous", "bucketed"])
    ap.add_argument("--cache-impl", default="paged", choices=["paged", "dense"])
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--pages", type=int, default=0,
                    help="page-pool size (0 = worst case)")
    ap.add_argument("--chunk", type=int, default=4,
                    help="prefill tokens per step per slot (continuous)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="mean arrivals per step (Poisson stream; 0 = all "
                         "queued at step 0)")
    ap.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                    help="run the engine tensor-parallel over a device "
                         "mesh, e.g. '1x2' (token streams bit-identical "
                         "to single-device on CPU host devices, not yet "
                         "on a TPU; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N first)")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens the step they are sampled")
    args = ap.parse_args()
    argv = [
        "--arch", args.arch, "--smoke",
        "--requests", str(args.requests), "--slots", str(args.slots),
        "--gen", str(args.gen), "--prompt-len", args.prompt_lens,
        "--shared-prefix", str(args.shared_prefix),
        "--scheduler", args.scheduler,
        "--cache-impl", args.cache_impl,
        "--prefix-cache", args.prefix_cache,
        "--page-size", str(args.page_size),
        "--pages", str(args.pages), "--chunk", str(args.chunk),
        "--arrival-rate", str(args.arrival_rate),
    ]
    if args.quant is not None and args.policy is not None:
        ap.error("--policy and the deprecated --quant are exclusive")
    if args.quant is not None:
        # deprecated alias: keeps working via QuantConfig.to_policy()
        argv += ["--quant", args.quant]
    else:
        argv += ["--policy", args.policy or "serve_fp8_paged"]
    if args.mesh is not None:
        argv += ["--mesh", args.mesh]
    if args.stream:
        argv.append("--stream")
    serve.main(argv)


if __name__ == "__main__":
    main()
