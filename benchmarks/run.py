# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark runner: executes every per-table module, writes one CSV row
per (table, configuration) as ``name,us_per_call,derived`` where
us_per_call is the per-document ingest cost and ``derived`` carries the
table's headline metric. Full rows also land in benchmarks/results/*.csv,
each naming the platform, device kind and device count it ran on. A
table that fails is reported and the rest still run, but the runner
then exits non-zero.
"""
from __future__ import annotations

import os
import sys
import time
import traceback

from benchmarks.common import device_info, write_csv

FAST = os.environ.get("REPRO_BENCH_FAST", "0") == "1"


def _tables():
    from benchmarks import (fig3_hyperparams, table3_accuracy_memory,
                            table4_latency_throughput, table5_cross_stream,
                            table6_memory_sweep, table7_basis_ablation,
                            table8_eviction_ablation,
                            table9_adaptive_ablation,
                            table10_11_pca_sensitivity,
                            table12_component_ablation, table13_downstream,
                            table14_two_stage, table15_sharded,
                            table17_quantized_store,
                            table18_ingest_throughput, table19_serve_fusion,
                            table20_overload, table21_hotset_cache,
                            table22_recovery)
    scale = 0.5 if FAST else 1.0

    def n(x):
        return max(6, int(x * scale))

    return [
        ("table3", lambda: table3_accuracy_memory.run(n_batches=n(40))),
        ("table4", lambda: table4_latency_throughput.run(n_batches=n(30))),
        ("table5", lambda: table5_cross_stream.run(n_batches=n(30))),
        ("table6", lambda: table6_memory_sweep.run(n_batches=n(20))),
        ("table7", lambda: table7_basis_ablation.run(n_batches=n(30))),
        ("table8", lambda: table8_eviction_ablation.run(n_batches=n(30))),
        ("table9", lambda: table9_adaptive_ablation.run(n_batches=n(30))),
        ("table10_11", lambda: table10_11_pca_sensitivity.run(n_batches=n(24))),
        ("table12", lambda: table12_component_ablation.run(n_batches=n(30))),
        ("table13", lambda: table13_downstream.run(n_batches=n(40))),
        ("table14", lambda: table14_two_stage.run(n_batches=n(40))),
        ("table15", lambda: table15_sharded.run(n_batches=n(24))),
        ("table17", lambda: table17_quantized_store.run(n_batches=n(24))),
        ("table18", lambda: table18_ingest_throughput.run(n_batches=n(24))),
        ("table19", lambda: table19_serve_fusion.run(reps=n(40))),
        ("table20", lambda: table20_overload.run(n_queries=n(600))),
        ("table21", lambda: table21_hotset_cache.run(n_timed=n(48))),
        ("table22", lambda: table22_recovery.run(n_batches=n(18))),
        ("fig3", lambda: fig3_hyperparams.run(n_batches=n(20))),
    ]


def _headline(row: dict) -> tuple[str, float, float]:
    name_parts = [str(row.get(k)) for k in
                  ("method", "stream", "basis", "strategy", "policy",
                   "variant", "param", "budget_mb", "window_W", "interval_T",
                   "alpha", "value")
                  if row.get(k) is not None]
    name = f"{row['table']}/" + "-".join(name_parts or ["_"])
    us = 1000.0 * float(row.get("ingest_latency_ms", 0.0) or 0.0)
    for key in ("recall10", "EM", "throughput_dps", "p50_speedup"):
        if key in row:
            return name, us, float(row[key])
    return name, us, 0.0


def main() -> None:
    from repro import obs

    os.makedirs("benchmarks/results", exist_ok=True)
    # metrics on for the whole sweep: serving counters/histograms from
    # every table land in one registry, dumped next to the CSV results
    obs.enable(metrics=True, trace=False)
    all_rows = []
    failed = []
    dev = device_info()
    print(f"# device: {dev}")
    print("name,us_per_call,derived")
    for tname, fn in _tables():
        t0 = time.time()
        try:
            rows = fn()
        except Exception:
            traceback.print_exc()
            print(f"{tname}/ERROR,0,0")
            failed.append(tname)
            continue
        rows = [{**dev, **row} for row in rows]  # child rows keep theirs
        all_rows.extend(rows)
        write_csv(f"benchmarks/results/{tname}.csv", rows)
        for row in rows:
            name, us, derived = _headline(row)
            print(f"{name},{us:.1f},{derived}")
        print(f"# {tname} done in {time.time()-t0:.1f}s", flush=True)
    write_csv("benchmarks/results/all.csv", all_rows)
    reg = obs.metrics()
    if reg is not None:
        reg.dump_json("benchmarks/results/metrics.json")
        print("# metrics snapshot -> benchmarks/results/metrics.json",
              flush=True)
    if failed:
        print(f"# FAILED tables: {', '.join(failed)}", flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
