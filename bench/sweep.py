#!/usr/bin/env python3
"""Find the query knee of a query cell once, on the chip.

    python3 bench/sweep.py --workload <query cell> --seed <n> --seconds 8 \
        --rates 4000,6000,8000,10000,12000

Sets the cell up once (the same set-up as ``bench/run.py``), then drives
one open-loop window per rate, in the given order, with the cell's
background ingest, and prints per rate the due-to-answer p50 and p99, how
many queries were still unanswered when the window closed, and the p50
of the last fifth of the window against the first fifth: a backlog that
grows shows as both rising. The cell's rate is set at about 0.8 of the
highest rate whose backlog does not grow. Not run by the benchmark.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]

    import jax
    import numpy as np

    from bench import cells, drive, schedule, series
    from repro.launch.compile_cache import enable_compile_cache

    cell = cells.load_cell(args.workload)
    if (jax.devices()[0].platform == "cpu") != args.cpu_rehearsal:
        print("sweep: needs a TPU (or --cpu-rehearsal on the CPU)",
              file=sys.stderr)
        return 2
    if not args.cpu_rehearsal:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    traffic = copy.deepcopy(cell.traffic)
    key = "rehearsal" if args.cpu_rehearsal else None
    (traffic[key] if key else traffic).setdefault("queries", {})[
        "rate_qps"] = max(rates)
    run = drive.Run(cell=cell.name, seed=args.seed, seconds=args.seconds,
                    rehearsal=args.cpu_rehearsal, config=cell.config,
                    traffic=traffic,
                    cfg=drive.pipeline_config(cell.config,
                                              args.cpu_rehearsal))
    window = drive.Window(run, None)
    try:
        window.setup()
        pool = run.pool
        ing = window.tr["ingest"]
        for i, rate in enumerate(rates):
            run.q_due = schedule.poisson_due(rate, args.seconds,
                                             args.seed + i)
            if len(pool) < len(run.q_due):
                pool = np.concatenate([pool, window.stream.queries(
                    len(run.q_due) - len(pool))])
            run.pool = pool[:len(run.q_due)]
            run.flushes, run.answers = [], {}
            run.batches_offered = 0
            for kept in (window.engine.kept, window.engine.captured,
                         window.engine.covering):
                kept.clear()
            window.batches = [window.stream.next_batch(ing["batch"])
                              for _ in range(len(run.b_due))]
            window._open_loop_window()
            lat = series.query_latency_ms(run)
            close = run.t_start + args.seconds
            fifth = len(lat) // 5
            row = {"rate_qps": rate, "queries": int(len(lat)),
                   "p50_ms": float(np.percentile(lat, 50)),
                   "p99_ms": float(np.percentile(lat, 99)),
                   "unanswered_at_close": int(np.sum(run.q_done > close)),
                   "p50_first_fifth_ms": float(np.median(lat[:fifth])),
                   "p50_last_fifth_ms": float(np.median(lat[-fifth:])),
                   "queries_per_flush": float(np.mean(
                       [f[3] for f in run.flushes])),
                   "fresh_p95_ms": float(np.percentile(
                       series.freshness_ms(run), 95))}
            print(json.dumps(row), flush=True)
    finally:
        window.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
