"""The program's own host spans in a profiler trace: time per span, and
the device's idle time put down to the span the host was in.

The fluid Monte-Carlo path marks its host phases with
``jax.profiler.TraceAnnotation`` spans named ``fluid.*`` (``fluid.query``,
``fluid.build`` and its parts, ``fluid.init``, per chunk ``fluid.launch``,
``fluid.sync``, ``fluid.retire``, ``fluid.compact``, then
``fluid.collect``).  They sit on the query thread beside the benchmark's
``mc_query`` span and nest there.  Only spans that start inside a query
span count.  A trace of a program without them gives an empty table, and
all of its idle time reads as unattributed.
"""

from __future__ import annotations

import dataclasses

from perfbench.lib import trace

PREFIX = "fluid."


@dataclasses.dataclass
class SpanStats:
    count: int = 0
    total_s: float = 0.0
    self_s: float = 0.0  # the span's time not covered by its child spans
    #: device idle time while it was the innermost span; ``None`` where
    #: the trace holds no device (a run on the CPU)
    idle_s: float | None = None


@dataclasses.dataclass
class Spans:
    queries: int
    idle_s: float | None  # device idle time in the query spans (first chip)
    unattributed_idle_s: float | None  # of it, under no program span
    table: dict  # span name -> SpanStats


def program_spans(host: list, spans: list) -> list:
    """The ``fluid.*`` events among ``host`` (start, end, name) that start
    inside one of ``spans``."""
    return [ev for ev in host
            if ev[2].startswith(PREFIX) and trace.inside(ev[0], spans)]


def self_intervals(events: list) -> list:
    """(start, end, name) pieces in which each span is the innermost: its
    interval less its children's.  Spans nest (one thread), so the pieces
    are disjoint; they come sorted by start."""
    out = []
    stack = []  # [end, name, cursor]: where the span's next piece starts

    def close(entry):
        if entry[2] < entry[0]:
            out.append((entry[2], entry[0], entry[1]))

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            parent = stack[-1]
            e = min(e, parent[0])
            if parent[2] < s:
                out.append((parent[2], s, parent[1]))
            parent[2] = e
        stack.append([e, name, s])
    while stack:
        close(stack.pop())
    return sorted(out)


def overlap_by_name(pieces: list, gaps: list) -> dict:
    """Summed overlap of sorted disjoint ``gaps`` with each name's sorted
    disjoint ``pieces``."""
    out = {}
    i = 0
    for s, e, name in pieces:
        while i < len(gaps) and gaps[i][1] <= s:
            i += 1
        j = i
        while j < len(gaps) and gaps[j][0] < e:
            lo, hi = max(s, gaps[j][0]), min(e, gaps[j][1])
            if lo < hi:
                out[name] = out.get(name, 0.0) + (hi - lo)
            j += 1
    return out


def idle_gaps(plane, spans: list) -> list:
    """Intervals inside ``spans`` in which no program ran on the device
    ``plane``: the gaps :func:`trace.reduce` reads, sorted."""
    modules = [(s, e) for s, e, _ in trace.line_events(plane, trace.MODULES)
               if trace.inside(s, spans)]
    busy = trace.merge(trace.clip(modules, spans))
    gaps = []
    for a, b in spans:
        edges = [a] + [x for iv in trace.clip(busy, [(a, b)]) for x in iv] + [b]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    return sorted(gaps)


def reduce(pd, span: str) -> Spans:
    spans, host = trace.query_thread(pd, span)
    if not spans:
        raise RuntimeError(f"trace holds no {span!r} span")
    events = program_spans(host, spans)
    pieces = self_intervals(events)
    table = {}
    for s, e, name in events:
        st = table.setdefault(name, SpanStats())
        st.count += 1
        st.total_s += (e - s) / 1e9
    for s, e, name in pieces:
        table[name].self_s += (e - s) / 1e9
    table = dict(sorted(table.items()))
    planes = trace.device_planes(pd)
    if not planes:
        return Spans(len(spans), None, None, table)
    gaps = idle_gaps(planes[0], spans)
    idle = overlap_by_name(pieces, gaps)
    for name, st in table.items():
        st.idle_s = idle.get(name, 0.0) / 1e9
    idle_s = sum(b - a for a, b in gaps) / 1e9
    return Spans(
        queries=len(spans), idle_s=idle_s,
        unattributed_idle_s=idle_s - sum(st.idle_s for st in table.values()),
        table=table,
    )


def format_table(s: Spans) -> str:
    """The span table, one line per span: count, total, self and idle
    time in milliseconds per query (idle "-" without a device)."""
    q = s.queries

    def ms(x):
        return f"{'-' if x is None else f'{1e3 * x / q:.3f}':>12}"

    rows = [f"{'span':<22}{'count':>8}{'total ms':>12}{'self ms':>12}"
            f"{'idle ms':>12}"]
    for name, st in s.table.items():
        rows.append(f"{name:<22}{st.count / q:>8.1f}{ms(st.total_s)}"
                    f"{ms(st.self_s)}{ms(st.idle_s)}")
    rows.append(f"{'(no span)':<22}{'':>8}{'':>12}{'':>12}"
                f"{ms(s.unattributed_idle_s)}")
    return "\n".join(rows)
