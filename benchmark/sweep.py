"""Find the rate an open-loop mix sustains: ONE sweep, on the chip, when a
cell is defined. Not part of a run; the rate it finds is written into the
traffic file by hand (about four fifths of the knee for a cell below it).

    python benchmark/sweep.py --workload <cell> --seed <n> --rates 100,200,400

One set-up, then for each rate a short window of the cell's own mix at
that rate. Prints, per rate, the latencies from the due times, how late
the generator ran, and how much the latency grew from the window's first
third to its last (a backlog that grows says the rate is past the knee).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402

from benchmark import cells, corpus, data, run, stats  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args()
    cell = cells.Cell(args.workload)
    cfg, mix = cell.config, cell.traffic
    seed, rows = args.seed % 2 ** 32, int(cfg["rows"])
    run_dir = os.path.join(corpus.CACHE, f"sweep-{cell.name}")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    entry, _ = corpus.ensure(cfg, seed, rows, rehearsal=False)
    _, queries, _ = data.make_data(cfg, seed, rows)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 1
    cl = run.Cluster(cfg, rows, run_dir)
    try:
        cl.serve_from(entry)
        cl.warm(queries, [int(r) for r in mix["warm_rows"]],
                run.warm_filters(cfg, mix, seed))
        pool_path = os.path.join(run_dir, "pool.npy")
        np.save(pool_path, queries)
        for rate in [float(r) for r in args.rates.split(",")]:
            cell.traffic = {**mix, "rate_per_s": rate, "warmup_s": 2.0}
            procs, outs, t0 = run.spawn_generators(
                cell, cfg, cl.cluster.router_addr, pool_path, run_dir, seed,
                args.seconds, profile=False)
            for p in procs:
                p.wait(timeout=args.seconds + run.DRAIN_S + 30)
            rec = run.gather(outs)
            v = run.window_view(cell.traffic, rec, t0, args.seconds)
            lat, due = v["lat_ms"], v["win"]["t_due"] - t0
            third = args.seconds / 3
            first, last = lat[due < third], lat[due >= 2 * third]
            print(json.dumps({
                "rate_per_s": rate, "attempted": v["attempted"],
                "failed": v["failed"],
                "p50_ms": stats.percentile(lat, 50),
                "p99_ms": stats.percentile(lat, 99),
                "late_p99_ms": stats.percentile(v["late_ms"], 99),
                "mean_first_third_ms": float(first.mean()),
                "mean_last_third_ms": float(last.mean()),
            }), flush=True)
            cl.drain_shadow_sampler()
    finally:
        cl.stop()
        shutil.rmtree(run_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
