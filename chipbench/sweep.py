"""Finds the highest arrival rate a cell's system sustains: serves the
cell's open-loop mix at each rate in ``--rates`` (its warm-up, then a
``--seconds`` window) on one engine, and prints per rate the work done
and whether the backlog grew.  Run once, by hand, to fix a cell's rate;
the benchmark's runs never search.

    python3 chipbench/sweep.py --workload <cell> --rates 0.2,0.3,0.4 \\
        --seconds 51 --seed 1
"""
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import cells, harness, stats  # noqa: E402


def backlog(run, t):
    """Requests due by ``t`` and not yet admitted."""
    return sum(1 for r in run.records if r.due <= t
               and (r.admitted is None or r.admitted > t))


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    base = cells.load_cell(args.workload)
    harness.configure_caches()
    harness.require_accelerator(base.chips)
    harness.import_program()
    server = harness.Server(base, profile=False)
    server.build(args.seed)
    server.warm_up()
    for rate in (float(r) for r in args.rates.split(",")):
        cell = dataclasses.replace(
            base, settings=dict(base.settings, rate_per_s=rate))
        t = time.perf_counter()
        run = harness.serve_window(server, cell, args.seed, args.seconds,
                                   trace=False, t_process=t)
        m = harness.read_metrics(run, cell.end_to_end)
        due = [r for r in run.records if run.t_open <= r.due < run.t_close]
        ttft, cens = stats.ttft_sample(run.records, run.t_open, run.t_close)
        half = run.t_open + args.seconds / 2
        first = [v for r, v in zip(due, ttft) if r.due < half]
        second = [v for r, v in zip(due, ttft) if r.due >= half]
        print(json.dumps({
            "rate_per_s": rate, "due": len(due), "censored": cens,
            "backlog_open": backlog(run, run.t_open),
            "backlog_mid": backlog(run, half),
            "backlog_close": backlog(run, run.t_close),
            "ttft_mean_first_half": sum(first) / max(len(first), 1),
            "ttft_mean_second_half": sum(second) / max(len(second), 1),
            "metrics": {k: v["value"] for k, v in m.items()}}), flush=True)
        for rid in list(run.sched.by_rid):
            run.sched.cancel(rid)
        run.sched = None
    return 0


if __name__ == "__main__":
    sys.exit(main())
