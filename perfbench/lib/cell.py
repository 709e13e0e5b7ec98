"""One run of one cell: set-up, the measured window (or the traced
query), and the comparison with the plain reference.

The window is a closed loop of Monte-Carlo queries.  Each query is one
call of the program's entry, ``repro.scenarios.monte_carlo_fluid``, on
``lanes`` new rollout seeds; queries run back to back until ``--seconds``
have passed, the one running then finishes and counts, and the rate is
every rollout completed over the whole elapsed time.

Query ``q`` of the window runs the same rollouts under every ``--seed``
(a fixed pool), in an order that ``--seed`` shuffles, so the seed does
not change the amount of work a window holds; ``--seed`` also draws the
rollouts that are compared with the reference.
"""

from __future__ import annotations

import gc
import importlib
import shutil
import sys
import tempfile
import time
import traceback
from types import SimpleNamespace

import numpy as np

from perfbench.lib import check, workload

#: jax.monitoring event recorded for every program the backend compiles,
#: or loads from the persistent compilation cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: host span around each query in a trace
QUERY_SPAN = "mc_query"
#: seed streams, so warm-up, window, guard and sample never share seeds
WINDOW, WARMUP, GUARD, SAMPLE = 0, 1, 2, 3
#: root of the fixed pool of rollouts that the window and warm-up run
POOL = 12
#: the warm-up's jobs have this many times fewer iterations, and arrive
#: over this many times shorter a horizon, than the cell's
WARM_CUT = 8
#: the warm-up makes jumps past a halving from batches of up to this many
#: lanes (:func:`compactions`)
JUMP_MAX = 16
#: distinct rollouts of the warm-up's first query, whose makespans pick
#: the short and the long rollout of the others
PROBE_LANES = 8


def lane_seeds(seed: int, stream: int, index: int, n: int) -> list:
    rng = np.random.default_rng([seed % 2**64, stream, index])
    return [int(s) for s in rng.integers(0, 2**62, n)]


def query_seeds(seed: int, q: int, n: int) -> list:
    """The rollout seeds of the window's query ``q``: the pool's, in the
    order that ``seed`` draws."""
    pool = lane_seeds(POOL, WINDOW, q, n)
    order = np.random.default_rng([seed % 2**64, WINDOW, q]).permutation(n)
    return [pool[i] for i in order]


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache."""

    def __init__(self, monitoring):
        self.count = 0
        self._monitoring = monitoring
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.count += 1

    def close(self):
        self._monitoring.unregister_event_duration_listener(self._on_event)


def accelerator(jax, chips: int, require: bool):
    """The devices the cell runs on, or ``None`` (with the reason on
    standard error) where JAX finds no accelerator or too few chips."""
    devices = jax.devices()
    if require and devices[0].platform == "cpu":
        print("no accelerator: JAX found only the CPU", file=sys.stderr)
        return None
    if require and len(devices) < chips:
        print(f"the cell needs {chips} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return None
    return devices


def set_up_jax(jax) -> None:
    """Persistent compilation cache in the checkout, holding every
    program however small or quick to compile, so the next run of the
    cell loads each one instead of compiling it."""
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Program:
    """The system under test: the Monte-Carlo entry, called on one
    configuration with the policy, placement and dt it states and the
    program's defaults for everything else."""

    def __init__(self, cfg: dict):
        from repro.scenarios import get_scenario, monte_carlo_fluid

        self.cfg = cfg
        self.get_scenario = get_scenario
        self._entry = monte_carlo_fluid

    def query(self, seeds: list, **scale) -> list:
        cfg = self.cfg
        return self._entry(
            cfg["scenario"], seeds, comm=cfg["policy"],
            placement=cfg["placement"],
            overrides={**cfg["scenario_overrides"], **scale}, dt=cfg["dt"],
        )


def warm_scale(cfg: dict) -> dict:
    """The warm-up's shorter jobs: fewer iterations and arrivals packed
    into a shorter horizon, the lanes and the job count kept."""
    return {
        "min_iters": max(1, cfg["min_iters"] // WARM_CUT),
        "max_iters": max(1, cfg["max_iters"] // WARM_CUT),
        "horizon_s": cfg["horizon_s"] / WARM_CUT,
    }


def compactions(lanes: int) -> list:
    """``(F, T)``: each move of lane compaction, from a batch of ``F``
    lanes to the power of two ``T`` that holds the lanes still live, that
    a query of ``lanes`` can make: every halving, and from batches of up
    to ``JUMP_MAX`` lanes every jump past one, since only there do a few
    lanes that end in one chunk skip a power of two."""
    out, f = [], lanes
    while f > 1:
        t = 1 << ((f - 1).bit_length() - 1)
        out.append((f, t))
        j = t // 2 if f <= JUMP_MAX else 0
        while j >= 1:
            out.append((f, j))
            j //= 2
        f = t
    return out


def warm_up(program: Program, cfg: dict, traffic: dict,
            counter: "CompileCounter") -> list:
    """Short queries that load, before the window, every program a query
    of the cell's lanes can reach.  Compaction builds programs for each
    pair of lane counts it moves between, so one query per move in
    :func:`compactions`: ``F - T`` lanes of the probe's shortest rollout
    and ``T`` of its longest, which end chunks apart and so move the
    batch from ``F`` to ``T`` lanes.  Returns ``(query, programs
    loaded)`` of each query."""
    lanes, scale, loaded = traffic["lanes"], warm_scale(cfg), []

    def run(seeds, label):
        before = counter.count
        recs = program.query(seeds, **scale)
        loaded.append((label, counter.count - before))
        return recs

    probe = lane_seeds(POOL, WARMUP, 0, min(lanes, PROBE_LANES))
    ends = [r.makespan for r in run(probe, str(len(probe)))]
    short, long = probe[ends.index(min(ends))], probe[ends.index(max(ends))]
    for f, t in compactions(lanes):
        run([short] * (f - t) + [long] * t, f"{f}>{t}")
    return loaded


def run_query(program: Program, seeds: list, log: list):
    """One query; ``None`` (and the traceback on standard error) if the
    program raised."""
    t0 = time.perf_counter()
    try:
        recs = program.query(seeds)
    except Exception as exc:  # a failed query is a result, not a crash
        traceback.print_exc()
        log.append(f"query of {len(seeds)} lanes raised {exc!r}")
        return None, time.perf_counter() - t0
    return recs, time.perf_counter() - t0


def window(program: Program, traffic: dict, seed: int, seconds: float,
           counter: CompileCounter):
    """The closed loop.  Returns (queries, elapsed seconds, errors)."""
    queries, errors = [], []
    t0 = time.perf_counter()
    q = 0
    while True:
        seeds = query_seeds(seed, q, traffic["lanes"])
        before = counter.count
        recs, took = run_query(program, seeds, errors)
        queries.append(SimpleNamespace(seeds=seeds, recs=recs, seconds=took,
                                       compiles=counter.count - before))
        q += 1
        if recs is None or time.perf_counter() - t0 >= seconds:
            return queries, time.perf_counter() - t0, errors


def traced_query(program: Program, jax, traffic: dict, seed: int, errors,
                 counter: CompileCounter):
    """One query of the window under the profiler; returns the query, the
    trace's reduction and its program spans (``lib/spans.py``)."""
    from perfbench.lib import spans, trace

    seeds = query_seeds(seed, 0, traffic["lanes"])
    before = counter.count
    tdir = tempfile.mkdtemp(prefix="perfbench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(QUERY_SPAN):
                recs, took = run_query(program, seeds, errors)
        finally:
            jax.profiler.stop_trace()
        summary, pd = trace.reduce_dir(tdir, QUERY_SPAN)
        program_spans = spans.reduce(pd, QUERY_SPAN)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    query = SimpleNamespace(seeds=seeds, recs=recs, seconds=took,
                            compiles=counter.count - before)
    return query, summary, program_spans


def sample(queries: list, k: int, seed: int) -> list:
    """(seed, record) of ``k`` finished rollouts drawn from ``seed``,
    the longest (largest makespan) among them."""
    pool = [(s, r) for q in queries if q.recs for s, r in zip(q.seeds, q.recs)]
    if len(pool) <= k:
        return pool
    longest = max(range(len(pool)), key=lambda i: pool[i][1].makespan)
    rest = [i for i in range(len(pool)) if i != longest]
    rng = np.random.default_rng([seed % 2**64, SAMPLE])
    picked = [longest] + sorted(rng.choice(rest, k - 1, replace=False).tolist())
    return [pool[i] for i in picked]


def compare(cfg: dict, queries: list, traffic: dict, seed: int) -> dict:
    """The numbers that decide ``correct``."""
    n_jobs = cfg["n_jobs"]
    unfinished = sum(
        len(q.seeds) if q.recs is None else
        sum(r.n_finished != n_jobs for r in q.recs)
        for q in queries
    )
    picked = sample(queries, traffic["sample_lanes"], seed)
    lanes = [workload.job_arrays(workload.generate(cfg, s)) for s, _ in picked]
    ref = workload.reference_of(cfg)(lanes, cfg) if lanes else []
    prog = [(r.n_finished, r.avg_jct, r.makespan) for _, r in picked]
    off = check.lanes_off(prog, ref)
    return {"lanes_unfinished": unfinished, "lanes_off": off}


def device_info(devices, peak) -> dict:
    dev = devices[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def memory_peak(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def read_metrics(names: list, ctx) -> dict:
    """Per-layer metrics: each is read by ``metrics/<name>.py`` from
    ``ctx`` (:func:`measure`); one that finds nothing to read is left
    out."""
    out = {}
    for name, unit in names:
        value = importlib.import_module(f"perfbench.metrics.{name}").read(ctx)
        if value is not None:
            out[name] = {"value": value, "unit": unit}
    return out


def measure(cell: dict, cfg: dict, traffic: dict, metrics: dict, *,
            seed: int, seconds: float, trace: bool, t_start: float,
            require_accelerator: bool = True):
    """Run the cell once; the result object, or ``None`` where the
    machine cannot run it.  ``metrics`` maps ``end_to_end`` and
    ``per_layer`` to the cell's ``(name, unit)`` pairs."""
    import jax

    devices = accelerator(jax, cell["chips"], require_accelerator)
    if devices is None:
        return None
    set_up_jax(jax)
    program = Program(cfg)
    workload.check_program_jobs(
        cfg, lane_seeds(seed, GUARD, 0, 3), program.get_scenario
    )
    counter = CompileCounter(jax.monitoring)
    try:
        warm = warm_up(program, cfg, traffic, counter)
        setup_s = time.perf_counter() - t_start
        errors = []
        summary = program_spans = None
        if trace:
            query, summary, program_spans = traced_query(
                program, jax, traffic, seed, errors, counter)
            queries, elapsed = [query], query.seconds
        else:
            queries, elapsed, errors = window(program, traffic, seed, seconds,
                                              counter)
    finally:
        counter.close()
    peak = memory_peak(devices)
    gc.collect()

    n_jobs = cfg["n_jobs"]
    attempted = sum(len(q.seeds) for q in queries)
    completed = sum(r.n_finished == n_jobs for q in queries if q.recs
                    for r in q.recs)
    values = compare(cfg, queries, traffic, seed)
    checks, ok = check.judge(values)
    # what a per-layer metric reads: each query's RunMetrics (None for a
    # query that raised), and in a traced run the trace's reduction and
    # its program spans
    ctx = SimpleNamespace(
        records=[q.recs for q in queries],
        chunks=[q.recs[0].chunks for q in queries if q.recs],
        compiles=sum(q.compiles for q in queries), trace=summary,
        spans=program_spans,
    )
    if trace:
        out = read_metrics(metrics["per_layer"], ctx)
    else:
        e2e = {"rollouts_per_s": completed / elapsed, "setup_s": setup_s}
        out = {n: {"value": e2e[n], "unit": u} for n, u in metrics["end_to_end"]}
    result = {
        "correct": ok and not errors,
        "attempted": attempted,
        "failed": attempted - completed,
        "metrics": out,
        "device": device_info(devices, peak),
    }
    if summary is not None:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown
    result["checks"] = checks
    print("warm-up (query, programs loaded): " + ", ".join(
        f"({n}, {c})" for n, c in warm), file=sys.stderr)
    print("queries (seconds, chunks, programs loaded): " + ", ".join(
        f"({q.seconds:.3f}, {q.recs[0].chunks if q.recs else None}, "
        f"{q.compiles})" for q in queries), file=sys.stderr)
    for e in errors:
        print(f"error: {e}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    return result
