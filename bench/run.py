#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` on the chip: set-up (engine init,
store fill, warming every shape the window uses), a measured window of
``--seconds``, then the comparison with the plain reference that decides
``correct``. The last line of stdout is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics read from a profiler window),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``, each
number compared beside its limit. The same numbers end stderr.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.

    JAX_PLATFORMS=cpu python3 bench/run.py --cpu-rehearsal --workload <cell>

runs the cell on the CPU at the configuration's tiny rehearsal size with
interpret-mode kernels; its result line names the CPU.

    python3 bench/run.py --control bf16 --workload <cell> ...

puts the reference computed in bfloat16 in the program's place; it must
come out as not correct. The program's own numbers from the same run are
printed on an earlier line.
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny size on the CPU, interpret-mode kernels")
    ap.add_argument("--control", choices=("bf16",),
                    help="compare the reference computed in bfloat16 "
                         "in the program's place")
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    t_main = time.perf_counter()
    args = parse(argv)
    from bench import cells

    try:
        cell = cells.load_cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        return fail(f"cannot load workload {args.workload!r}: {e}")
    try:
        import jax

        from repro import obs
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        return fail(f"the program is not importable here: {e}")
    devs = jax.devices()
    platform = devs[0].platform
    if args.cpu_rehearsal != (platform == "cpu"):
        return fail(f"platform is {platform!r}: a run needs a TPU, and "
                    f"--cpu-rehearsal needs the CPU")
    if not args.cpu_rehearsal and platform != "tpu":
        return fail(f"platform {platform!r} is not a TPU")
    if len(devs) < cell.chips:
        return fail(f"the cell needs {cell.chips} chips, JAX found "
                    f"{len(devs)}")
    from bench import drive, report

    if not args.cpu_rehearsal:
        print(f"compile cache: {enable_compile_cache()}", flush=True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if args.trace:
        obs.enable(metrics=False, trace=True)
    else:
        obs.disable()
    cfg = drive.pipeline_config(cell.config, args.cpu_rehearsal)
    run = drive.Run(cell=cell.name, seed=args.seed, seconds=args.seconds,
                    rehearsal=args.cpu_rehearsal, config=cell.config,
                    traffic=cell.traffic, cfg=cfg)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    window = drive.Window(run, trace_dir, obs.tracer())
    jax.monitoring.register_event_duration_secs_listener(
        window.on_compile_event)
    try:
        window.setup()
        run.setup_s = time.perf_counter() - t_main
        window.run_window()
        run.memory_peak_bytes = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devs[:cell.chips])
        window.close()
        if args.control:
            own = report.check(run, window.engine)
            run.notes.append("program's own checks (correct "
                             f"{own['correct']}): {own['compared']}")
        checks = report.check(run, window.engine, control=args.control)
        if args.trace:
            report.read_trace(run, window, trace_dir)
        line = report.result(run, cell, checks, devs[:cell.chips],
                             len(devs), platform, bool(args.trace))
    finally:
        obs.disable()
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        window.close()
    report.emit(run, checks, line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
