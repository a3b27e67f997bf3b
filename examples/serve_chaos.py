"""Fault-tolerant serving demo: chaos injection + crash recovery.

Runs the same request stream twice through the crash-safe serving loop
(``repro.runtime.fault.run_serving``): once clean, once under a seeded
``FaultPlan`` — page seizures, preemption storms, refcount-corruption
detection drills, watchdog overruns, and an engine kill recovered from an
on-disk snapshot — then diffs the two runs.  Every request that finishes
under chaos must emit exactly the clean run's tokens (stochastic FP8 KV
rounding ON); requests that blow their deadline or get shed fail alone.

Run:  PYTHONPATH=src python examples/serve_chaos.py \
          [--arch qwen2-0.5b] [--requests 8] [--slots 3] [--gen 8] \
          [--prompt-lens 6,10,4,8] [--pages 10] [--arrival-rate 0.7] \
          [--deadline-steps 26] [--max-queue 6] \
          [--seed 1] [--exhaustion 0.25] [--storm 0.15] \
          [--corruption 0.15] [--overrun 0.2] [--kill-at-step 12] \
          [--snapshot-every 4] [--ckpt-dir DIR]
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import argparse
import tempfile

import numpy as np

from repro.caches import enable_compile_cache

EPILOG = """\
fault plan (all per-step probabilities from one seeded stream):
  --exhaustion P     seize pages off the free list for a few steps
  --storm P          spill every active slot but the oldest
  --corruption P     refcount-corruption detection drill (must be caught
                     by the pool invariant checker, then repaired)
  --overrun P        rewind the step watchdog so the deadline trips
  --kill-at-step N   raise a simulated engine crash before step N; the
                     engine is rebuilt and restored from the latest
                     snapshot under --ckpt-dir (cold replay if none)

examples:
  # the default chaos schedule, kill at step 12, snapshot every 4 steps
  python examples/serve_chaos.py
  # pure crash/recovery: no probabilistic faults, just the kill
  python examples/serve_chaos.py --exhaustion 0 --storm 0 \\
      --corruption 0 --overrun 0 --kill-at-step 8 --snapshot-every 2
  # overload shedding only: tight queue + deadline, no chaos at all
  python examples/serve_chaos.py --kill-at-step -1 --exhaustion 0 \\
      --storm 0 --corruption 0 --overrun 0 --deadline-steps 15 --max-queue 2
"""


def main():
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        epilog=EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--prompt-lens", default="6,10,4,8",
                    help="comma list of prompt lengths, cycled over requests")
    ap.add_argument("--page-size", type=int, default=4)
    ap.add_argument("--pages", type=int, default=10,
                    help="page-pool size (small = contention; 0 = worst case)")
    ap.add_argument("--arrival-rate", type=float, default=0.7,
                    help="mean arrivals per step (Poisson stream)")
    ap.add_argument("--deadline-steps", type=int, default=26,
                    help="per-request step budget (0 = none)")
    ap.add_argument("--max-queue", type=int, default=6,
                    help="queued arrivals beyond this are shed (0 = none)")
    ap.add_argument("--seed", type=int, default=1, help="FaultPlan seed")
    ap.add_argument("--exhaustion", type=float, default=0.25)
    ap.add_argument("--storm", type=float, default=0.15)
    ap.add_argument("--corruption", type=float, default=0.15)
    ap.add_argument("--overrun", type=float, default=0.2)
    ap.add_argument("--kill-at-step", type=int, default=12,
                    help="engine kill before this step (-1 = no kill)")
    ap.add_argument("--snapshot-every", type=int, default=4)
    ap.add_argument("--ckpt-dir", default=None,
                    help="snapshot directory (default: a tempdir)")
    args = ap.parse_args()
    enable_compile_cache()

    from repro.configs import get_config
    from repro.launch import serve
    from repro.runtime import fault
    from repro.serving import FaultPlan

    cfg = get_config(args.arch, smoke=True, policy="serve_fp8_paged")
    rng = np.random.default_rng(0)
    plens = [int(x) for x in args.prompt_lens.split(",")]
    queue = [rng.integers(0, cfg.vocab, size=plens[i % len(plens)])
             for i in range(args.requests)]
    arrivals = None
    if args.arrival_rate > 0:
        gaps = rng.exponential(1.0 / args.arrival_rate, size=len(queue))
        arrivals = np.floor(np.cumsum(gaps)).astype(int)

    def make_engine():
        return serve.Engine(
            cfg, slots=args.slots, max_seq=24, cache_impl="paged",
            page_size=args.page_size,
            num_pages=args.pages or None, stochastic_kv=True,
        )

    knobs = dict(
        gen=args.gen, arrivals=arrivals, chunk=4,
        deadline_steps=args.deadline_steps or None,
        max_queue=args.max_queue or None,
        watermark_high=0.95, watermark_low=0.6,
    )
    print(f"# clean run: {args.requests} requests, {args.slots} slots, "
          f"pool={args.pages or 'worst-case'} pages")
    base, base_stats = fault.run_serving(
        make_engine, [q.copy() for q in queue], **knobs)
    print(f"# clean: steps={base_stats['steps']} "
          f"tok/s={base_stats['tok_s']:.2f} "
          f"terminal={base_stats['terminal']}")

    plan = FaultPlan(
        seed=args.seed, pool_exhaustion=args.exhaustion,
        exhaustion_pages=2, exhaustion_hold=3,
        preemption_storm=args.storm, corruption=args.corruption,
        overrun=args.overrun,
        kill_at_step=None if args.kill_at_step < 0 else args.kill_at_step,
    )
    print(f"# chaos run: {plan}")
    ckpt = args.ckpt_dir or tempfile.mkdtemp(prefix="serve_chaos_")
    out, stats = fault.run_serving(
        make_engine, [q.copy() for q in queue], **knobs,
        chaos=plan, ckpt_dir=ckpt, snapshot_every=args.snapshot_every,
        step_deadline_s=3600.0,
        heartbeat_path=pathlib.Path(ckpt) / "heartbeat.json",
    )
    c = stats["chaos"]
    print(f"# chaos: steps={stats['steps']} tok/s={stats['tok_s']:.2f} "
          f"terminal={stats['terminal']}")
    print(f"# faults: exhaustion={c['exhaustion']} storm={c['storm']} "
          f"corruption_drills={c['corruption']} overrun={c['overrun']} "
          f"killed={c['killed']} restarts={stats['restarts']} "
          f"snapshots={stats['snapshots']}")
    for rid, (state, reason) in sorted(stats["statuses"].items()):
        mark = ""
        if state == "finished":
            mark = ("== clean" if out.get(rid) == base.get(rid)
                    else "!! DIVERGED")
        print(f"  rid={rid:<3d} {state:<10s} {reason or '-':<28s} {mark}")
    survivors_equal = len(out) > 0 and all(
        out[rid] == base.get(rid) for rid in out)
    print(f"# survivors_equal={int(survivors_equal)} "
          f"({len(out)} finished under chaos, every one bit-identical to "
          "the clean run)" if survivors_equal else
          f"# survivors_equal=0 ({len(out)} finished; MISMATCH)")
    if not survivors_equal:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
