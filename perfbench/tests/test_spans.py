"""The program-span reduction (``lib/spans.py``) on hand-made events and
on the recorded v5e trace, and ``span_table.py`` run whole at a small
size on the CPU."""

import gzip
import importlib.util
import shutil
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from perfbench.lib import spans, trace, workload

RECORDED = Path(__file__).parent / "data" / "small.xplane.pb.gz"
TOOL = Path(__file__).resolve().parents[1] / "span_table.py"
S = 1_000_000_000  # ns


def ev(name, start, end):
    return NS(name=name, start_ns=float(start * S), end_ns=float(end * S))


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def made_up():
    """One query span [0, 10 s).  The device runs in [3.1, 4.9) and
    [6.05, 6.9), and once after the query; the host thread holds a query
    of two chunks, with a gap in [7, 7.2) under ``fluid.query`` alone,
    and one program span after the query."""
    device = plane(
        "/device:TPU:0",
        XLA_Modules=[ev("jit__chunk_jit(2)", 3.1, 4.9),
                     ev("jit__chunk_jit(2)", 6.05, 6.9),
                     ev("jit__chunk_jit(2)", 11, 12)],
    )
    host = plane("/host:CPU", python3=[
        ev("mc_query", 0, 10),
        ev("fluid.query", 0.5, 9.5),
        ev("fluid.build", 0.5, 3),
        ev("fluid.build.scenario", 0.5, 1),
        ev("fluid.build.encode", 1, 2),
        ev("PjitFunction(convert_element_type)", 1.2, 1.3),
        ev("fluid.build.stack", 2, 3),
        ev("fluid.launch", 3, 3.2),
        ev("fluid.sync", 3.2, 5),
        ev("fluid.retire", 5, 5.5),
        ev("fluid.compact", 5.5, 6),
        ev("fluid.launch", 6, 6.1),
        ev("fluid.sync", 6.1, 7),
        ev("fluid.collect", 7.2, 9.5),
        ev("fluid.query", 11, 12),
    ])
    return NS(planes=[host, device])


def test_innermost_span_takes_the_idle_time():
    s = spans.reduce(made_up(), "mc_query")
    idle = {n: st.idle_s for n, st in s.table.items()}
    assert idle == pytest.approx({
        "fluid.query": 0.2, "fluid.build": 0.0,
        "fluid.build.scenario": 0.5, "fluid.build.encode": 1.0,
        "fluid.build.stack": 1.0, "fluid.launch": 0.15, "fluid.sync": 0.2,
        "fluid.retire": 0.5, "fluid.compact": 0.5, "fluid.collect": 2.3,
    })
    assert s.idle_s == pytest.approx(7.35)


def test_self_time_leaves_out_children():
    t = spans.reduce(made_up(), "mc_query").table
    assert t["fluid.build"].total_s == pytest.approx(2.5)
    assert t["fluid.build"].self_s == pytest.approx(0.0)
    assert t["fluid.query"].total_s == pytest.approx(9.0)
    assert t["fluid.query"].self_s == pytest.approx(0.2)
    assert t["fluid.sync"].count == 2
    assert t["fluid.sync"].self_s == t["fluid.sync"].total_s == pytest.approx(2.7)


def test_idle_under_no_span_is_unattributed():
    s = spans.reduce(made_up(), "mc_query")
    assert s.unattributed_idle_s == pytest.approx(1.0)  # [0, 0.5), [9.5, 10)


def test_spans_outside_the_query_are_ignored():
    t = spans.reduce(made_up(), "mc_query").table
    assert t["fluid.query"].count == 1
    assert not any(n.startswith("PjitFunction") for n in t)


@pytest.mark.parametrize("events, want", [
    # a child that starts with its parent
    ([(0, 10, "a"), (0, 4, "b"), (6, 10, "c")],
     [(0, 4, "b"), (4, 6, "a"), (6, 10, "c")]),
    # three levels
    ([(0, 10, "a"), (1, 9, "b"), (2, 3, "c")],
     [(0, 1, "a"), (1, 2, "b"), (2, 3, "c"), (3, 9, "b"), (9, 10, "a")]),
    # a child that outlasts its parent is cut at the parent's end
    ([(0, 5, "a"), (3, 7, "b"), (8, 9, "c")],
     [(0, 3, "a"), (3, 5, "b"), (8, 9, "c")]),
])
def test_self_intervals(events, want):
    assert spans.self_intervals(events) == want


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    from jax.profiler import ProfileData

    path = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    with gzip.open(RECORDED, "rb") as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    return ProfileData.from_file(str(path))


def test_recorded_trace_has_no_program_spans(recorded):
    """A program without spans: an empty table, every idle instant
    unattributed, and the idle time :func:`trace.reduce` reads."""
    s = spans.reduce(recorded, "mc_query")
    t = trace.reduce(recorded, "mc_query")
    assert s.table == {}
    assert s.unattributed_idle_s == s.idle_s
    assert s.idle_s == pytest.approx(t.window_s - t.busy_s, rel=1e-9)


def tool():
    spec = importlib.util.spec_from_file_location("span_table", TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_span_table_small():
    small = dict(n_jobs=12, min_iters=10, max_iters=40, horizon_s=60.0)
    cfg = workload.load_config("paper")
    cfg.update(small)
    cfg["scenario_overrides"] = dict(small)
    (line,) = tool().trace_queries(
        {"chips": 1}, cfg, {"lanes": 4, "sample_lanes": 4}, [2**31 + 5],
        require_accelerator=False)
    c, sp = line["counters"], line["spans"]
    assert sp["fluid.query"]["count"] == 1
    assert sp["fluid.launch"]["count"] == sp["fluid.sync"]["count"] == c["chunks"]
    assert c["live_lane_slots"] <= c["lane_slots"]
    assert line["programs_loaded"] >= 0
    assert line["idle_s"] is None  # no device on the CPU
    assert all(st["idle_s"] is None for st in sp.values())
    assert line["query_s"] <= line["span_s"]
