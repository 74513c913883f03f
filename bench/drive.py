"""Set-up and the measured window of one cell.

The entry the window drives is ``serve.runtime.AsyncServer`` over
``engine.engine.Engine``, through its public calls only: ``submit`` and
``flush`` for queries, ``ingest`` and ``sync`` for the stream. The server
is handed a :class:`RecordingEngine`, a thin delegating wrapper that
records each publication (its version, the time it returned and the docs
it covers) and keeps the snapshots and states the correctness check
reads after the window.

One general load generator reads every traffic mix: a mix may hold open-loop
queries (``queries``: rate, flush size, and ``two_stage`` false for
prototype-only answers), an ingest stream that is either open-loop at a
fixed rate or a flood offered as fast as ``ingest`` accepts it
(``ingest``), and a store fill before the window (``fill_share``).

The process's garbage collector is left as the program leaves it: every
collection after set-up is timed and reported on an earlier line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import threading
import time

import numpy as np

from bench import schedule
from bench.stream import make_stream

# The profiler window of a traced run: it opens at this share of the
# window and lasts TRACE_SECONDS, at most TRACE_MAX_SHARE of the window.
TRACE_START_SHARE = 0.4
TRACE_SECONDS = 3.0
TRACE_MAX_SHARE = 0.3
# A fill checks the live slots every quarter of the ring slots' worth of
# docs, and gives up after this many docs per slot.
FILL_CHECKS_PER_STORE = 4
FILL_MAX_DOCS_PER_SLOT = 24
# The flood's generator keeps GENERATOR_DEPTH batches in the making on
# GENERATOR_THREADS threads.
GENERATOR_DEPTH = 16
GENERATOR_THREADS = 4


# ------------------------------------------------------------ configuration
def pipeline_config(config: dict, rehearsal: bool):
    """The program's ``PipelineConfig`` for a configuration file; the full
    size is checked against ``budget_to_config`` and the file's state
    bytes. The rehearsal size overrides keys with the file's
    ``rehearsal`` ones and uses interpret-mode kernels."""
    from repro.configs.streaming_rag import paper_pipeline_config
    from repro.core import heavy_hitter, pipeline

    size = {**config, **config["rehearsal"]} if rehearsal else config
    cfg = paper_pipeline_config(
        dim=size["dim"], k=size["num_clusters"], capacity=size["hh_capacity"],
        alpha=size["alpha"], admit_prob=size["admit_prob"],
        basis=size["basis"], policy=heavy_hitter.Policy[size["hh_policy"]],
        update_interval=size["update_interval"],
        store_depth=size["store_depth"], store_dtype=size["store_dtype"])
    if rehearsal:
        return dataclasses.replace(cfg, clus=dataclasses.replace(
            cfg.clus, use_pallas=True))
    sized = pipeline.budget_to_config(
        config["budget_mb"], config["dim"], paper_pipeline_config(
            dim=config["dim"], alpha=config["alpha"],
            store_depth=config["store_depth"],
            store_dtype=config["store_dtype"]))
    if sized != cfg:
        raise ValueError(f"{config['name']}: the file's sizes are not what "
                         f"budget_to_config gives ({sized})")
    if pipeline.state_memory_bytes(cfg) != config["state_memory_bytes"]:
        raise ValueError(f"{config['name']}: state bytes "
                         f"{pipeline.state_memory_bytes(cfg)} differ from "
                         f"the file's {config['state_memory_bytes']}")
    return cfg


def traffic_params(traffic: dict, rehearsal: bool) -> dict:
    """The mix with its rehearsal overrides applied (one level deep)."""
    if not rehearsal:
        return traffic
    out = {k: (dict(v) if isinstance(v, dict) else v)
           for k, v in traffic.items() if k != "rehearsal"}
    for key, val in traffic.get("rehearsal", {}).items():
        if isinstance(val, dict):
            out[key] = {**out.get(key, {}), **val}
        else:
            out[key] = val
    return out


# ---------------------------------------------------------- recording engine
STATE_BEFORE = ("clus.centroids", "hh.labels", "hh.counts", "store.ids",
                "store.ptr", "pre.basis")
STATE_AFTER = ("hh.labels", "hh.counts", "store.ids", "store.ptr",
               "store.embs", "store.scales")


def _leaf(state, path: str):
    for part in path.split("."):
        state = getattr(state, part)
    return state


class RecordingEngine:
    """Delegates to an ``Engine``; records every publication and keeps
    what the check needs: the snapshots of sampled versions, the route
    index of versions published while the profiler runs, copies of the
    state around sampled ingest calls (device copies, no host sync) and
    the first snapshot published after each of those calls.
    """

    def __init__(self, engine):
        self._engine = engine
        self.docs = 0               # live docs handed to ingest so far
        self.calls = 0              # ingest calls so far
        self.publishes: list = []   # (version, perf_counter, docs)
        self.last = None            # newest snapshot
        self.keep_version = None    # version -> bool, set by the window
        self.kept: dict = {}        # version -> snapshot
        self.keep_index = False
        self.index_of: dict = {}    # version -> (vectors, valid, labels)
        self.capture_calls: set = set()
        self.captured: dict = {}    # ingest call -> (before, after, ids)
        self.covering: dict = {}    # ingest call -> first snapshot after it

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def ingest(self, x, doc_ids):
        import jax.numpy as jnp

        n = self.calls
        before = None
        if n in self.capture_calls:
            st = self._engine.state
            before = {p: jnp.copy(_leaf(st, p)) for p in STATE_BEFORE}
        info = self._engine.ingest(x, doc_ids)
        if before is not None:
            st = self._engine.state
            after = {p: jnp.copy(_leaf(st, p)) for p in STATE_AFTER}
            self.captured[n] = (before, after, np.asarray(doc_ids))
        self.docs += int(np.sum(np.asarray(doc_ids) >= 0))
        self.calls = n + 1
        return info

    def warm_copies(self):
        """Compile the state copies a sampled ingest call takes, so that
        none compiles inside the window."""
        import jax
        import jax.numpy as jnp

        st = self._engine.state
        jax.block_until_ready([jnp.copy(_leaf(st, p)) for p in
                               set(STATE_BEFORE) | set(STATE_AFTER)])

    def publish(self):
        snap = self._engine.publish()
        self.publishes.append((snap.version, time.perf_counter(), self.docs))
        self.last = snap
        for n in self.captured:
            self.covering.setdefault(n, snap)
        if self.keep_version is not None and self.keep_version(snap.version):
            self.kept[snap.version] = snap
        if self.keep_index:
            self.index_of[snap.version] = (snap.index.vectors,
                                           snap.index.valid,
                                           snap.route_labels)
        return snap


# ----------------------------------------------------------------- the run
@dataclasses.dataclass
class Run:
    """Everything one run recorded; metric readers and checks read it."""

    cell: str
    seed: int
    seconds: float
    rehearsal: bool
    config: dict
    traffic: dict
    cfg: object                   # the program's PipelineConfig
    setup_s: float = 0.0
    t_start: float = 0.0          # perf_counter at the window start
    compiles_in_window: int = 0
    # queries (open-loop)
    q_due: np.ndarray | None = None     # s from t_start
    q_sent: np.ndarray | None = None    # perf_counter
    q_done: np.ndarray | None = None    # perf_counter, nan = unanswered
    pool: np.ndarray | None = None
    flushes: list = dataclasses.field(default_factory=list)
    answers: dict = dataclasses.field(default_factory=dict)  # kept only
    # ingest
    b_due: np.ndarray | None = None     # open-loop batches, s from t_start
    b_docs_before: int = 0              # docs handed to ingest before
    b_size: int = 0
    batches_offered: int = 0
    batches_failed: int = 0
    gen_empty_s: float = 0.0
    server_idle_share: float | None = None
    window_docs: list = dataclasses.field(default_factory=list)  # offered
    # (generation, start s from t_start, seconds) of each collection
    gc_window: list = dataclasses.field(default_factory=list)
    # recorded by the engine wrapper and the tracer
    engine: RecordingEngine | None = None
    publish_spans_ms: list = dataclasses.field(default_factory=list)
    trace: object = None          # devtrace.View of the profiler window
    trace_host: tuple | None = None   # perf_counter bounds of that window
    memory_peak_bytes: int = 0
    peak: dict | None = None
    host_cache: dict = dataclasses.field(default_factory=dict)
    notes: list = dataclasses.field(default_factory=list)


class Window:
    """Set up the server for a cell and drive its measured window."""

    def __init__(self, run: Run, trace_dir: str | None, obs_tracer=None):
        self.run = run
        self.trace_dir = trace_dir
        # the program's span tracer (traced runs) and its clock's offset
        # from perf_counter, read when the profiler starts
        self.obs_tracer = obs_tracer
        self.obs_clock = None
        self.tr = traffic_params(run.traffic, run.rehearsal)
        self.gen = None
        self.server = None
        self.engine = None
        self.batches: list = []       # the open-loop window's batches
        self._compile_events = 0
        self._compile_s = 0.0
        self._profiling = False
        self._gc_t0 = None

    # ---------------------------------------------------------- bookkeeping
    def on_compile_event(self, event: str, duration: float, **_):
        if event.startswith("/jax/core/compile/"):
            self._compile_events += 1
            self._compile_s += duration

    def _on_gc(self, phase: str, info: dict):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.run.gc_window.append(
                (info["generation"], self._gc_t0 - self.run.t_start,
                 time.perf_counter() - self._gc_t0))
            self._gc_t0 = None

    def _ann(self, name: str, **kw):
        if self.trace_dir is None:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name, **kw)

    # --------------------------------------------------------------- set-up
    def setup(self):
        import jax

        from repro.engine.engine import Engine
        from repro.serve.runtime import AsyncServer, ServerConfig

        run, tr = self.run, self.tr
        cfg = run.cfg
        dim = cfg.clus.dim
        g = run.config["guarantees"]
        marks = [("start", time.perf_counter(), self._compile_events,
                  self._compile_s)]

        def mark(name):
            marks.append((name, time.perf_counter(), self._compile_events,
                          self._compile_s))
        ing = tr["ingest"]
        flood = ing["mode"] == "flood"
        if flood:
            from bench.gen import Generator

            self.gen = Generator(tr["stream"], dim, run.seed, ing["batch"],
                                 GENERATOR_DEPTH, GENERATOR_THREADS)
            warm = [self.gen.get() for _ in range(
                tr["warmup_docs"] // ing["batch"])]
        else:
            self.stream = make_stream(tr["stream"], dim, run.seed)
            wb = tr["warmup_batch"]
            warm = [self.stream.next_batch(wb)
                    for _ in range(tr["warmup_docs"] // wb)]
        warm_x = np.concatenate([w[0] for w in warm])
        key = jax.random.key(run.seed % (2**31 - 1))
        self.engine = RecordingEngine(Engine(cfg, key, warmup=warm_x))
        run.engine = self.engine
        self.engine.warm_copies()
        mark("init")
        q = tr.get("queries") or {}
        scfg = ServerConfig(
            max_batch=q.get("max_batch", 32),
            max_wait_ms=q.get("max_wait_ms", 2.0),
            topk=g["topk"], two_stage=q.get("two_stage", True),
            nprobe=g["nprobe"],
            adaptive=g["adaptive"], cache_entries=g["cache_entries"],
            hotset=g["hotset"])
        if tr.get("fill_share", 0) > 0:
            self.server = AsyncServer(cfg, scfg, engine=self.engine,
                                      publish_every=tr["fill_publish_every"])
            self._fill(tr)
            self.server.close()
            mark("fill")
        self.server = AsyncServer(cfg, scfg, engine=self.engine,
                                  publish_every=ing["publish_every"])
        if flood:
            for _ in range(ing["compile_batches"]):
                x, ids = self.gen.get()
                self.server.ingest(x, ids)
            self.server.sync()
        else:
            self._setup_open_loop(tr, q)
        mark("warm-up")
        run.notes.append("set-up phases (s, compiles, compile s): "
                         + ", ".join(f"{b[0]} {b[1] - a[1]:.3f} {b[2] - a[2]}"
                                     f" {b[3] - a[3]:.3f}"
                                     for a, b in zip(marks, marks[1:])))
        run.notes.append(
            f"garbage collector after set-up: thresholds {gc.get_threshold()}"
            f", counts {gc.get_count()}, tracked objects "
            f"{len(gc.get_objects())}, collections so far "
            f"{[st['collections'] for st in gc.get_stats()]}")
        gc.callbacks.append(self._on_gc)

    def _fill(self, tr):
        """Ingest until the publish-time device counters show
        ``fill_share`` of the ring slots live, checking at a fixed
        cadence (so a seed always does the same work)."""
        run = self.run
        fb = tr["fill_batch"]
        size = run.config["rehearsal"] if run.rehearsal else run.config
        slots = size["num_clusters"] * size["store_depth"]
        every = max(1, slots // (FILL_CHECKS_PER_STORE * fb))
        while True:
            for _ in range(every):
                x, ids = self.stream.next_batch(fb)
                self.server.ingest(x, ids)
            self.server.sync()
            c = self.engine.device_counters()
            if c["store_live"] >= tr["fill_share"] * c["store_slots"]:
                run.notes.append(
                    f"store fill: {c['store_live']}/{c['store_slots']} slots "
                    f"live after {self.engine.docs} docs")
                return
            if self.engine.docs >= FILL_MAX_DOCS_PER_SLOT * slots:
                raise RuntimeError(
                    f"store only {c['store_live']}/{c['store_slots']} live "
                    f"after {self.engine.docs} docs")

    def _setup_open_loop(self, tr, q):
        """Make the window's traffic and warm every shape it can produce:
        each query batch size up to ``max_batch`` (the uncached path does
        not pad) and the open-loop ingest batch."""
        run, server = self.run, self.server
        ing = tr["ingest"]
        if q:
            run.q_due = schedule.poisson_due(q["rate_qps"], run.seconds,
                                             run.seed)
            run.pool = self.stream.queries(max(len(run.q_due),
                                               q["max_batch"]))
            for n in range(1, q["max_batch"] + 1):
                for i in range(n):
                    server.submit(run.pool[i])
                got = server.flush()
                if len(got) != n:
                    raise RuntimeError(f"warm-up flush of {n} answered "
                                       f"{len(got)}")
        run.b_due = schedule.periodic_due(ing["rate_docs_s"] / ing["batch"],
                                          run.seconds)
        self.batches = [self.stream.next_batch(ing["batch"])
                        for _ in range(len(run.b_due)
                                       + ing["warm_batches"])]
        for x, ids in self.batches[:ing["warm_batches"]]:
            server.ingest(x, ids)
        server.sync()
        self.batches = self.batches[ing["warm_batches"]:]

    # ------------------------------------------------------------ the window
    def sampled_versions(self, expected_publishes: float, want: int):
        """Keep every ``stride``-th version from the window start, with a
        phase drawn from the seed."""
        v0 = self.engine.last.version
        stride = max(1, int(expected_publishes // max(want, 1)))
        phase = int(np.random.default_rng(self.run.seed).integers(stride))
        keep = lambda v: v >= v0 and (v - v0) % stride == phase  # noqa: E731
        if keep(v0):
            self.engine.kept[v0] = self.engine.last
        self.engine.keep_version = keep
        return stride

    def sample_ingest_calls(self, lo: int, hi: int, n: int):
        """Capture the state around ``n`` ingest calls of the window,
        drawn from the seed among its calls ``lo`` to ``hi - 1``."""
        picks = np.random.default_rng(self.run.seed).choice(
            np.arange(lo, hi), size=min(n, max(hi - lo, 0)), replace=False)
        first = self.engine.calls
        self.engine.capture_calls = {first + int(p) for p in picks}

    def run_window(self):
        """Drive the window. A traced run reads the trace over the
        ``bench.window`` annotation inside it. Starting the profiler
        stalls the host for seconds, so a mix whose load would pile up
        behind the stall starts it before the window
        (``profiler_before_window``); a mix whose device work would
        overflow the profiler's buffer over a whole window starts it at
        the annotation and stops it after."""
        flood = self.tr["ingest"]["mode"] == "flood"
        try:
            if (self.trace_dir is not None
                    and self.tr["profiler_before_window"]):
                self._start_profiler()
            if flood:
                self._flood_window()
            else:
                self._open_loop_window()
        finally:
            self._stop_profiler()
            if self.gen is not None:
                self.gen.close()
                self.gen = None

    def _start_profiler(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._profiling = True

    def _stop_profiler(self):
        import jax

        if self._profiling:
            jax.profiler.stop_trace()
            self._profiling = False

    def _trace_plan(self):
        """(start, stop) of the profiler window, s from the window start."""
        if self.trace_dir is None:
            return None
        start = TRACE_START_SHARE * self.run.seconds
        return start, start + min(TRACE_SECONDS,
                                  TRACE_MAX_SHARE * self.run.seconds)

    def _start_trace(self):
        """Open the ``bench.window`` annotation the trace is read over
        (starting the profiler first if it is not running)."""
        if not self._profiling:
            self._start_profiler()
        self.engine.keep_index = True
        last = self.engine.last
        self.engine.index_of[last.version] = (
            last.index.vectors, last.index.valid, last.route_labels)
        if self.obs_tracer is not None:
            self.obs_clock = (self.obs_tracer.now_us(), time.perf_counter())
        ann = self._ann("bench.window")
        ann.__enter__()
        return ann, time.perf_counter()

    def _stop_trace(self, ann, t0):
        t1 = time.perf_counter()
        ann.__exit__(None, None, None)
        self.engine.keep_index = False
        self.run.trace_host = (t0, t1)
        if not self.tr["profiler_before_window"]:
            self._stop_profiler()

    def _open_loop_window(self):
        run, server, tr = self.run, self.server, self.tr
        n_q = 0 if run.q_due is None else len(run.q_due)
        run.q_sent = np.full(n_q, np.nan)
        run.q_done = np.full(n_q, np.nan)
        run.b_docs_before = self.engine.docs
        run.b_size = tr["ingest"]["batch"]
        run.window_docs = self.batches
        ing = tr["ingest"]
        n_b = len(run.b_due)
        self.sampled_versions(
            run.seconds * ing["rate_docs_s"] / ing["batch"]
            / ing["publish_every"], tr["check"]["versions"])
        self.sample_ingest_calls(1, n_b - 1, tr["check"]["batches"])
        state = {"submitted": 0, "answered": 0, "stop": False,
                 "error": None, "ticket0": None, "seq": 0}
        kept = self.engine.kept
        lock = threading.Lock()

        def flusher():
            try:
                while True:
                    with lock:
                        if state["stop"] and \
                                state["answered"] >= state["submitted"]:
                            return
                    seq = state["seq"]
                    state["seq"] = seq + 1
                    t0 = time.perf_counter()
                    with self._ann("bench.flush", seq=seq):
                        out = server.flush()
                    t1 = time.perf_counter()
                    if not out:
                        time.sleep(1e-4)
                        continue
                    t0q = state["ticket0"]
                    idx = np.fromiter((o["ticket"] - t0q for o in out),
                                      np.int64, len(out))
                    version = out[0]["snapshot_version"]
                    run.q_done[idx] = t1
                    run.flushes.append((seq, t0, t1, len(out), version, idx))
                    if version in kept:
                        for i, o in zip(idx.tolist(), out):
                            run.answers[i] = (version, o["doc_ids"],
                                              o["scores"], o["clusters"])
                    with lock:
                        state["answered"] += len(out)
            except BaseException as e:  # surfaced on the main thread
                state["error"] = e
                with lock:
                    state["stop"] = True

        plan = self._trace_plan()
        ann = t_tr0 = None
        thread = threading.Thread(target=flusher, name="bench-flusher",
                                  daemon=True)
        thread.start()
        qi = bi = 0
        t_start = time.perf_counter()
        run.t_start = t_start
        c0 = self._compile_events
        try:
            while qi < n_q or bi < n_b:
                if state["error"] is not None:
                    break
                now = time.perf_counter() - t_start
                if plan is not None:
                    if ann is None and t_tr0 is None and now >= plan[0]:
                        ann, t_tr0 = self._start_trace()
                        continue
                    if ann is not None and now >= plan[1]:
                        self._stop_trace(ann, t_tr0)
                        ann = None
                        continue
                while qi < n_q and run.q_due[qi] <= now:
                    ticket = server.submit(run.pool[qi])
                    run.q_sent[qi] = time.perf_counter()
                    if state["ticket0"] is None:
                        state["ticket0"] = ticket
                    with lock:
                        state["submitted"] += 1
                    qi += 1
                while bi < n_b and run.b_due[bi] <= now:
                    x, ids = self.batches[bi]
                    with self._ann("bench.ingest"):
                        server.ingest(x, ids)
                    run.batches_offered += 1
                    bi += 1
                nxt = min(run.q_due[qi] if qi < n_q else np.inf,
                          run.b_due[bi] if bi < n_b else np.inf)
                if plan is not None:
                    nxt = min(nxt, plan[0] if t_tr0 is None else
                              (plan[1] if ann is not None else np.inf))
                wait = nxt - (time.perf_counter() - t_start)
                if 0 < wait < np.inf:
                    time.sleep(wait)
            # the window has closed: wait for every due request
            rest = max(0.0, run.seconds - (time.perf_counter() - t_start))
            time.sleep(rest)
            run.compiles_in_window = self._compile_events - c0
            if ann is not None:
                self._stop_trace(ann, t_tr0)
        finally:
            with lock:
                state["stop"] = True
            thread.join(120)
        if state["error"] is not None:
            raise state["error"]
        if thread.is_alive():
            raise RuntimeError("flusher did not finish within 120 s")
        server.sync()

    def _flood_window(self):
        run, server, tr = self.run, self.server, self.tr
        ing = tr["ingest"]
        run.b_size = ing["batch"]
        run.b_docs_before = self.engine.docs
        self.sample_ingest_calls(*tr["check"]["batch_range"],
                                 tr["check"]["batches"])
        plan = self._trace_plan()
        ann = t_tr0 = None
        empty_calls = 0
        empty0 = self.gen.empty_s
        t_start = time.perf_counter()
        run.t_start = t_start
        c0 = self._compile_events
        while True:
            now = time.perf_counter() - t_start
            if now >= run.seconds:
                break
            if plan is not None:
                if ann is None and t_tr0 is None and now >= plan[0]:
                    ann, t_tr0 = self._start_trace()
                elif ann is not None and now >= plan[1]:
                    self._stop_trace(ann, t_tr0)
                    ann = None
            with self._ann("bench.generate"):
                x, ids = self.gen.get()
            fs = server.freshness_stats()
            empty_calls += fs["docs_enqueued"] == fs["docs_ingested"]
            with self._ann("bench.ingest"):
                server.ingest(x, ids)
            run.batches_offered += 1
            run.window_docs.append((x, ids))
        run.compiles_in_window = self._compile_events - c0
        run.gen_empty_s = self.gen.empty_s - empty0
        if ann is not None:
            self._stop_trace(ann, t_tr0)
        run.server_idle_share = empty_calls / max(run.batches_offered, 1)
        server.sync()

    def close(self):
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        if self.gen is not None:
            self.gen.close()
            self.gen = None
        if self.server is not None:
            self.server.close()
