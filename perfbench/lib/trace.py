"""Reduction of a profiler trace (``.xplane.pb``) to per-layer numbers.

What a TPU trace of this program holds (read by hand on a v5e trace):

* a plane ``/device:TPU:<n>`` per chip, with a line ``XLA Modules`` (one
  event per program execution, named ``jit_<function>(<hash>)``, e.g.
  ``jit__chunk_jit(...)``) and a line ``XLA Ops`` (one event per HLO
  instruction executed, named by its HLO text ``%<name>.<n> = ...``;
  control flow such as ``%while`` spans the instructions of its body);
* a plane ``/host:CPU`` whose lines are host threads; the benchmark's
  ``mc_query`` span sits on the Python thread's line beside jax's own
  events there (``PjitFunction(...)``, ``np.asarray(jax.Array)``, ...).

Device and host events share one clock in nanoseconds.  Only what lies
inside the query spans counts.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

MODULES = "XLA Modules"
OPS = "XLA Ops"
CHUNK_PROGRAM = "jit__chunk_jit("
TOP = 10


@dataclasses.dataclass
class Summary:
    queries: int
    window_s: float  # summed length of the query spans
    busy_s: float  # union of program executions in them, mean over chips
    chunk_s: float  # chunk program's device time, mean over chips
    lead_s: float | None  # span starts to their first chunk program
    breakdown: dict


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] ...`` -> ``fusion.12``."""
    cut = text.find(" = ")
    head = text if cut < 0 else text[:cut]
    return head[1:] if head.startswith("%") else head


def merge(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals: list, spans: list) -> list:
    """Parts of ``intervals`` that lie inside ``spans`` (both sorted
    lists of (start, end) pairs, ``spans`` disjoint)."""
    out = []
    for s, e in intervals:
        for a, b in spans:
            lo, hi = max(s, a), min(e, b)
            if lo < hi:
                out.append((lo, hi))
    return out


def inside(start, spans) -> bool:
    return any(a <= start < b for a, b in spans)


def leaf_op_times(line, spans) -> tuple:
    """Device time per instruction name over the ``XLA Ops`` events that
    start inside ``spans`` and contain no other event (control-flow
    instructions span their bodies' instructions).  Streams the line: its
    events come in order of start, and their HLO texts are too many to
    hold."""
    times = defaultdict(float)
    stack = []  # [end, name, duration, has_child]
    last = None

    def close(entry):
        if not entry[3]:
            times[entry[1]] += entry[2]

    for e in line.events:
        s, end = e.start_ns, e.end_ns
        if last is not None and s < last:
            raise RuntimeError(f"{OPS} events out of order at {s} ns")
        last = s
        if not inside(s, spans):
            continue
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        has_child = False
        while stack and stack[-1][0] < end:  # same start, and this one is longer
            close(stack.pop())
            has_child = True
        if stack:
            stack[-1][3] = True
        stack.append([end, op_name(e.name), end - s, has_child])
    while stack:
        close(stack.pop())
    return times


def device_planes(pd) -> list:
    return [p for p in pd.planes if p.name.startswith("/device:")
            and any(ln.name == MODULES for ln in p.lines)]


def line(plane, name: str):
    return next((ln for ln in plane.lines if ln.name == name), None)


def line_events(plane, name: str) -> list:
    ln = line(plane, name)
    return [] if ln is None else [(e.start_ns, e.end_ns, e.name) for e in ln.events]


def query_thread(pd, span: str):
    """(span intervals, other events of the thread that holds them)."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            evs = [(e.start_ns, e.end_ns, e.name) for e in ln.events]
            spans = [(s, e) for s, e, n in evs if n == span]
            if spans:
                return sorted(spans), [ev for ev in evs if ev[2] != span]
    return [], []


def label_gap(gap, host: list) -> str:
    """What the host's query thread was doing in a device idle gap: the
    event that overlaps it most, the shorter on a tie."""
    best, best_key = "no host event", None
    for s, e, n in host:
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap > 0:
            key = (overlap, -(e - s))
            if best_key is None or key > best_key:
                best, best_key = n, key
    return best


def reduce(pd, span: str) -> Summary:
    spans, host = query_thread(pd, span)
    planes = device_planes(pd)
    if not spans or not planes:
        raise RuntimeError(
            f"trace holds {len(spans)} {span!r} spans and {len(planes)} "
            "device planes with program executions"
        )
    busy = chunk = 0.0
    op_time = defaultdict(float)
    first_busy = None
    for k, plane in enumerate(planes):
        modules = [m for m in line_events(plane, MODULES) if inside(m[0], spans)]
        union = merge(clip([(s, e) for s, e, _ in modules], spans))
        busy += sum(e - s for s, e in union)
        chunk += sum(e - s for s, e, n in modules if n.startswith(CHUNK_PROGRAM))
        ops = line(plane, OPS)
        if ops is not None:
            for name, t in leaf_op_times(ops, spans).items():
                op_time[name] += t
        if k == 0:
            first_busy, chunk_starts = union, sorted(
                s for s, _, n in modules if n.startswith(CHUNK_PROGRAM))
    n = len(planes)
    lead = [next((c for c in chunk_starts if c >= a), None) for a, _ in spans]
    gaps = []
    for a, b in spans:
        edges = [a] + [x for iv in clip(first_busy, [(a, b)]) for x in iv] + [b]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return Summary(
        queries=len(spans),
        window_s=sum(b - a for a, b in spans) / 1e9,
        busy_s=busy / n / 1e9,
        chunk_s=chunk / n / 1e9,
        lead_s=(None if None in lead else
                sum(c - a for c, (a, _) in zip(lead, spans)) / 1e9),
        breakdown={
            "device_ops": [[name, t / n / 1e9] for name, t in sorted(
                op_time.items(), key=lambda kv: kv[1], reverse=True)[:TOP]],
            "idle_gaps": [[label_gap(g, host), (g[1] - g[0]) / 1e9]
                          for g in gaps[:TOP]],
        },
    )


def reduce_dir(trace_dir: str, span: str) -> tuple:
    """Read the one ``.xplane.pb`` that a profiler session wrote; returns
    its reduction and the ``ProfileData``, for other readers of the same
    trace."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    pd = ProfileData.from_file(paths[0])
    return reduce(pd, span), pd
