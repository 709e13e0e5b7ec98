"""The trace reduction, on hand-made events and on a small trace recorded
on a TPU v5e (two rollouts of eight short jobs, one traced query)."""

import gzip
import shutil
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from perfbench.lib import trace

RECORDED = Path(__file__).parent / "data" / "small.xplane.pb.gz"
S = 1_000_000_000  # ns


def ev(name, start, end):
    return NS(name=name, start_ns=float(start), end_ns=float(end),
              duration_ns=float(end - start))


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


KERNEL = ('%fluid_step_core_pallas.11 = (f32[8,1,16]) custom-call(f32[8,160,16] '
          '%a), custom_call_target="tpu_custom_call"')


def made_up():
    """One query span [0, 10 s); the device runs an init program, two
    chunk programs and one program outside the span; the host thread
    builds traces, then waits."""
    device = plane(
        "/device:TPU:0",
        XLA_Modules=[ev("jit__init_jit(1)", 1 * S, 2 * S),
                     ev("jit__chunk_jit(2)", 3 * S, 5 * S),
                     ev("jit__chunk_jit(2)", 6 * S, 7 * S),
                     ev("jit__chunk_jit(2)", 11 * S, 12 * S)],
        XLA_Ops=[ev("%while.6 = (s32[]) while(...)", 3 * S, 5 * S),
                 ev(KERNEL, 3 * S, 4 * S),
                 ev("%fusion.1 = f32[8] fusion(...)", 4 * S, 5 * S),
                 ev("%while.6 = (s32[]) while(...)", 6 * S, 7 * S),
                 ev(KERNEL, 6 * S, 6.5 * S),
                 ev("%fluid_step_core_pallas.11 = f32[8] copy(...)",
                    6.5 * S, 7 * S),
                 ev(KERNEL, 11 * S, 12 * S)],
    )
    host = plane(
        "/host:CPU",
        python3=[ev("mc_query", 0, 10 * S),
                 ev("PjitFunction(convert_element_type)", 0, 1 * S),
                 ev("PjitFunction(_take)", 5 * S, 5.5 * S),
                 ev("np.asarray(jax.Array)", 5.5 * S, 10 * S)],
    )
    return NS(planes=[host, device])


@pytest.mark.parametrize("child_first", [False, True])
def test_made_up_trace(child_first):
    pd = made_up()
    if child_first:  # a child listed before its container of equal start
        ops = pd.planes[1].lines[1].events
        ops[0], ops[1] = ops[1], ops[0]
    s = trace.reduce(pd, "mc_query")
    assert s.queries == 1
    assert s.window_s == 10.0
    assert s.busy_s == 4.0
    assert s.chunk_s == 3.0
    assert s.lead_s == 3.0
    ops = dict(s.breakdown["device_ops"])
    assert "while.6" not in ops  # a container, not an operation
    assert ops["fusion.1"] == 1.0
    gaps = s.breakdown["idle_gaps"]
    assert [g[1] for g in gaps] == [3.0, 1.0, 1.0, 1.0]
    assert gaps[0][0] == "np.asarray(jax.Array)"
    assert {g[0] for g in gaps[1:]} == {
        "PjitFunction(convert_element_type)", "PjitFunction(_take)",
        "no host event"}


def test_trace_without_span_raises():
    pd = made_up()
    pd.planes[0].lines[0].events = pd.planes[0].lines[0].events[1:]
    with pytest.raises(RuntimeError, match="mc_query"):
        trace.reduce(pd, "mc_query")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    from jax.profiler import ProfileData

    path = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    with gzip.open(RECORDED, "rb") as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    return ProfileData.from_file(str(path))


def test_recorded_trace(recorded):
    s = trace.reduce(recorded, "mc_query")
    assert s.queries == 1
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.chunk_s <= s.busy_s
    assert 0 < s.lead_s < s.window_s
    ops = dict(s.breakdown["device_ops"])
    assert any(name.startswith("fluid_step_core_pallas") for name in ops)
    assert all(not name.startswith("while") for name in ops)
    assert 0 < len(s.breakdown["idle_gaps"]) <= trace.TOP
    idle = s.window_s - s.busy_s
    assert sum(g[1] for g in s.breakdown["idle_gaps"]) <= idle * (1 + 1e-9)


def test_recorded_trace_by_hand(recorded):
    """Chunk time and busy time as a plain pass over the events gives
    them."""
    spans = [(e.start_ns, e.end_ns) for p in recorded.planes
             for ln in p.lines for e in ln.events if e.name == "mc_query"]
    (a, b), = spans
    dev = next(p for p in recorded.planes if p.name.startswith("/device:TPU"))
    mods = next(ln for ln in dev.lines if ln.name == "XLA Modules").events
    mods = [e for e in mods if a <= e.start_ns < b]
    chunk = sum(e.duration_ns for e in mods if e.name.startswith("jit__chunk_jit("))
    ticks = set()
    for e in mods:
        ticks.update(range(int(e.start_ns) // 1000, int(min(e.end_ns, b)) // 1000))
    s = trace.reduce(recorded, "mc_query")
    assert s.chunk_s == pytest.approx(chunk / 1e9, rel=1e-12)
    assert s.busy_s == pytest.approx(len(ticks) / 1e6, rel=0.05)
