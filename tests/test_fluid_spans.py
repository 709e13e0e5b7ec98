"""Host spans and driver counters of the fluid Monte-Carlo path.

``monte_carlo_fluid`` marks each host phase with a profiler span
(``jax.profiler.TraceAnnotation``) and returns the batched driver's
query-wide counters in every ``RunMetrics``.  A tiny batch of four lanes
that end chunks apart is run under the profiler and read back from the
trace file."""

from __future__ import annotations

import jax
import pytest

from repro.scenarios import monte_carlo_fluid

SMALL = dict(n_jobs=6, min_iters=5, max_iters=40, horizon_s=20.0)
SEEDS = [1, 2, 3, 4]  # makespans 17.7-27.1 s: lanes retire chunks apart
CHUNK = 64  # ticks per chunk: a few chunks a query

#: each span and the span that encloses it
PARENT = {
    "fluid.query": None,
    "fluid.build": "fluid.query",
    "fluid.build.scenario": "fluid.build",
    "fluid.build.encode": "fluid.build",
    "fluid.build.stack": "fluid.build",
    "fluid.init": "fluid.query",
    "fluid.launch": "fluid.query",
    "fluid.sync": "fluid.query",
    "fluid.retire": "fluid.query",
    "fluid.compact": "fluid.query",
    "fluid.collect": "fluid.query",
}


def run(**kw):
    return monte_carlo_fluid("paper", SEEDS, overrides=SMALL,
                             chunk_steps=CHUNK, **kw)


def answers(recs):
    """What each rollout computed, to the bit."""
    return [(r.seed, r.n_finished, r.avg_jct, r.median_jct, r.p95_jct,
             r.p99_jct, r.makespan) for r in recs]


def trace_spans(tdir, query):
    """``query()``'s records, and its program spans: (start, end, name,
    stats) of the thread that holds them."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tdir))
    try:
        recs = query()
    finally:
        jax.profiler.stop_trace()
    (path,) = tdir.rglob("*.xplane.pb")
    threads = [
        [(e.start_ns, e.end_ns, e.name, dict(e.stats)) for e in ln.events
         if e.name.startswith("fluid.")]
        for p in ProfileData.from_file(str(path)).planes for ln in p.lines
    ]
    threads = [t for t in threads if t]
    assert len(threads) == 1, "the spans lie on more than one thread"
    return recs, sorted(threads[0], key=lambda ev: (ev[0], -ev[1]))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(records with the profiler off, records and program spans with
    it on)."""
    plain = run()
    return (plain, *trace_spans(tmp_path_factory.mktemp("trace"), run))


def parents(spans):
    """The innermost span around each span (``None`` at the top)."""
    out, stack = [], []
    for s, e, name, _ in spans:
        while stack and stack[-1][1] <= s:
            stack.pop()
        assert not stack or e <= stack[-1][1], f"{name} outlasts its parent"
        out.append(stack[-1][2] if stack else None)
        stack.append((s, e, name))
    return out


def test_every_span_nests_as_listed(traced):
    _, _, spans = traced
    assert {name for _, _, name, _ in spans} == set(PARENT)
    for (_, _, name, _), parent in zip(spans, parents(spans)):
        assert parent == PARENT[name], name
    once = [n for n in PARENT if n not in
            ("fluid.launch", "fluid.sync", "fluid.retire", "fluid.compact")]
    for name in once:
        assert sum(n == name for _, _, n, _ in spans) == 1, name
    (query,) = [st for _, _, n, st in spans if n == "fluid.query"]
    assert query["lanes"] == len(SEEDS)


def test_stack_span_carries_the_transfer(traced):
    """``fluid.build.stack``: the lanes, padded job width and bytes of
    the one host-to-device transfer of the batch."""
    from repro.core.jaxsim import stack_traces, trace_from_jobs
    from repro.scenarios import get_scenario

    _, _, spans = traced
    (st,) = [st for _, _, n, st in spans if n == "fluid.build.stack"]
    batch = stack_traces([
        trace_from_jobs(s.job_list(), fusion=s.fusion)
        for s in (get_scenario("paper", seed=seed, **SMALL) for seed in SEEDS)])
    assert st["lanes"] == len(SEEDS)
    assert st["jobs"] == batch["arrival"].shape[1] == SMALL["n_jobs"]
    assert st["bytes"] == sum(v.nbytes for v in batch.values())


def test_launches_carry_their_chunk(traced):
    _, recs, spans = traced
    launches = [st for _, _, n, st in spans if n == "fluid.launch"]
    assert [st["chunk"] for st in launches] == list(range(recs[0].chunks))
    syncs = [st["chunk"] for _, _, n, st in spans if n == "fluid.sync"]
    assert syncs == list(range(recs[0].chunks))
    for name in ("fluid.retire", "fluid.compact"):
        chunks = [st["chunk"] for _, _, n, st in spans if n == name]
        assert chunks and set(chunks) <= set(syncs), name


def test_counters_are_exact(traced):
    _, recs, spans = traced
    launches = [(st["lanes"], st["jobs"]) for _, _, n, st in spans
                if n == "fluid.launch"]
    r = recs[0]
    assert all(
        (x.chunks, x.lane_slots, x.live_lane_slots, x.compactions, x.shapes)
        == (r.chunks, r.lane_slots, r.live_lane_slots, r.compactions, r.shapes)
        for x in recs)
    assert r.lane_slots == sum(lanes for lanes, _ in launches)
    assert r.chunks <= r.live_lane_slots <= r.lane_slots
    assert r.shapes == len(set(launches))
    assert r.compactions >= 1
    # a compaction always changes the launch shape
    assert r.compactions == sum(a != b for a, b in zip(launches, launches[1:]))


def test_spans_leave_the_answers_alone(traced):
    plain, recs, _ = traced
    assert answers(recs) == answers(plain)


def test_no_compaction_one_shape():
    (r, *_) = run(compact=False)
    assert r.compactions == 0
    assert r.shapes == 1
    assert r.lane_slots == len(SEEDS) * r.chunks


def test_launches_carry_the_fabric_uplinks(traced, tmp_path):
    """``uplinks``: the fabric's uplink domains, none on ``paper``'s
    NICs, one per rack of the two-tier ``oversub_fabric``."""
    _, _, spans = traced
    assert {st["uplinks"] for _, _, n, st in spans if n == "fluid.launch"} == {0}
    _, spans = trace_spans(tmp_path, lambda: monte_carlo_fluid(
        "oversub_fabric", SEEDS[:2], chunk_steps=CHUNK,
        overrides={**SMALL, "n_servers": 8, "servers_per_rack": 2}))
    assert {st["uplinks"] for _, _, n, st in spans if n == "fluid.launch"} == {4}
