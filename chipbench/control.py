"""Readings that set a cell's correctness limits: the program's numbers
on several seeds and, on the seeds named by ``--control-seeds``, the
control's (the reference computed in the next lower precisions, read at
the same positions of the same prompts and served tokens).  One process
serves every seed, so set-up is paid once.  The benchmark's own runs
never run the control.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3 --seconds 51

Prints one JSON line per seed and a summary: the lower reading (largest
program number) and the upper reading (smallest control number) of each
compared number.
"""
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import cells, harness  # noqa: E402


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    cell = cells.load_cell(args.workload)
    harness.configure_caches()
    device = harness.require_accelerator(cell.chips)
    harness.import_program()
    server = harness.Server(cell, profile=False)
    lower, upper = {}, {}
    for seed in seeds:
        t = time.perf_counter()
        server.build(seed)
        server.warm_up()
        run = harness.serve_window(server, cell, seed, args.seconds,
                                   trace=False, t_process=t)
        metrics = harness.read_metrics(run, cell.end_to_end)
        run.sched = None
        server.close()
        sample = harness.check_sample(run, int(cell.settings["check"]
                                               ["requests"]))
        g, cg = harness.compare(run, sample, control=seed in ctl)
        line = {"seed": seed, "tokens": int(sum(x.size for x in g)),
                "program": harness.numbers(g), "metrics": metrics,
                "device": device,
                "requests": [[r.rid, len(r.prompt), int(x.size),
                              float(x.mean()), float(x.max())]
                             + ([float(c.mean()), float(c.max())]
                                if cg is not None else [])
                             for r, x, c in zip(sample, g, cg or g)]}
        for k, v in line["program"].items():
            lower[k] = max(lower.get(k, v), v)
        if cg is not None:
            line["control"] = harness.numbers(cg)
            for k, v in line["control"].items():
                upper[k] = min(upper.get(k, v), v)
        print(json.dumps(line), flush=True)
    print(json.dumps({"lower": lower, "upper": upper,
                      "seeds": seeds, "control_seeds": sorted(ctl)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
