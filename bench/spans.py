"""The program's own host spans in a profiler trace, and the device's
idle time split by them.

The program marks its host work with ``jax.profiler.TraceAnnotation``
spans named ``pac.*`` (``EdgeSession.step``, the activation cache and
its prefetcher), on the same clock as the device's ops. Their stats
(``nbytes``, ``n``, ...) are the spans' keyword arguments.

* ``load`` reads the ``.xplane.pb`` as ``bench.trace.load`` does and
  keeps, per line, the stats of the ``pac.*`` events in a field of
  their own (``"stats"``: event index -> {stat: value}; a line without
  it has none);
* ``reduce`` splits every idle interval of device 0 inside
  ``bench.window`` at span boundaries, and gives each piece to the
  innermost ``pac.*`` or ``bench.*`` span covering it on the host line
  that holds ``bench.window`` (the thread that runs the steps), or to
  ``bench.window`` where there is none: self time, so the values sum to
  the window's idle time. Spans of other threads (the prefetcher's
  worker) overlap the steps and take no idle time; ``program_spans``
  counts and times every ``pac.*`` span that starts in the window, on
  any host line;
* ``of(record)`` is what a per-layer reader calls: the reduction of the
  record's trace, made once per record and kept at
  ``record["trace"]["program"]``.
"""

from __future__ import annotations

import bisect
import collections
import os

from bench import trace

PREFIX = "pac."
ATTRIBUTED = (PREFIX, trace.SPAN_PREFIX)
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          ".bench_trace")


def load(trace_dir: str) -> list:
    """Like ``bench.trace.load``; each line also has ``"stats"``."""
    from jax.profiler import ProfileData

    from bench.harness import trace_file

    data = ProfileData.from_file(trace_file(trace_dir))
    planes = []
    for p in data.planes:
        lines = []
        for ln in p.lines:
            events, stats = [], {}
            for e in ln.events:
                if e.name.startswith(PREFIX):
                    stats[len(events)] = dict(e.stats)
                events.append((e.name, float(e.start_ns), float(e.duration_ns)))
            lines.append({"name": ln.name, "events": events, "stats": stats})
        planes.append({"name": p.name, "lines": lines})
    return planes


def _host_lines(planes):
    return [ln for p in planes if p["name"].startswith("/host:") for ln in p["lines"]]


def _window(planes):
    for ln in _host_lines(planes):
        for n, s, d in ln["events"]:
            if n == trace.WINDOW_SPAN:
                return ln, s, s + d
    raise ValueError(f"no {trace.WINDOW_SPAN!r} span in the trace")


def _idle_gaps(planes, w0, w1):
    """Device 0's idle intervals inside the window (as ``trace.reduce``)."""
    ops = trace.device_ops(planes)[0]
    merged = trace._union(trace._clip([(s, e) for _, s, e in ops if e > w0 and s < w1],
                                      w0, w1))
    edges = [w0] + [x for ab in merged for x in ab] + [w1]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def _segments(spans, w0, w1):
    """The window cut at every span boundary: (bounds, owner), where
    owner[i] is the index in ``spans`` of the innermost (shortest) span
    covering [bounds[i], bounds[i + 1]), or None."""
    bounds = sorted({w0, w1} | {min(max(x, w0), w1) for _, s, e, _ in spans for x in (s, e)})
    opening = collections.defaultdict(list)
    for i, (_, s, e, _) in enumerate(spans):
        opening[max(s, w0)].append(i)
    active, owner = set(), []
    for a in bounds[:-1]:
        active.update(opening.get(a, ()))
        active = {i for i in active if spans[i][2] > a}
        owner.append(min(active, key=lambda i: (spans[i][2] - spans[i][1], -spans[i][1]))
                     if active else None)
    return bounds, owner


def _tally() -> dict:
    return {"count": 0, "s": 0.0, "nbytes": 0}


def _count(tally: dict, dur_ns: float, stats: dict) -> None:
    tally["count"] += 1
    tally["s"] += dur_ns / 1e9
    tally["nbytes"] += int(stats.get("nbytes", 0))


def reduce(planes) -> dict:
    """``window_s``; ``idle_by_program_span_s``, device 0's idle seconds
    by the innermost span of the window's line; ``program_spans``, per
    ``pac.*`` name {count, s, nbytes[, first]}."""
    line, w0, w1 = _window(planes)
    own = [(n, s, s + d, line.get("stats", {}).get(i, {}))
           for i, (n, s, d) in enumerate(line["events"])
           if n.startswith(ATTRIBUTED) and n != trace.WINDOW_SPAN and s + d > w0 and s < w1]
    bounds, owner = _segments(own, w0, w1)

    idle = collections.Counter()   # span index (None: the window) -> ns
    for a, b in _idle_gaps(planes, w0, w1):
        k = bisect.bisect_right(bounds, a) - 1
        while k < len(owner) and bounds[k] < b:
            idle[owner[k]] += min(b, bounds[k + 1]) - max(a, bounds[k])
            k += 1
    by_name = collections.Counter()
    for i, ns in idle.items():
        by_name[trace.WINDOW_SPAN if i is None else own[i][0]] += ns / 1e9

    # per pac.* name: count, summed duration and bytes of the spans that
    # start in the window; for a span with an ordinal ``n`` (the
    # prefetcher's wait), the same for n == 0 under "first", with its idle
    totals = {}
    for ln in _host_lines(planes):
        for j, (n, s, d) in enumerate(ln["events"]):
            if n.startswith(PREFIX) and w0 <= s < w1:
                st = ln.get("stats", {}).get(j, {})
                t = totals.setdefault(n, _tally())
                _count(t, d, st)
                if "n" in st:
                    first = t.setdefault("first", dict(_tally(), idle_s=0.0))
                    if st["n"] == 0:
                        _count(first, d, st)
    for i, (n, s, _, st) in enumerate(own):
        if st.get("n") == 0 and w0 <= s:
            totals[n]["first"]["idle_s"] += idle.get(i, 0) / 1e9
    return {"window_s": (w1 - w0) / 1e9,
            "idle_by_program_span_s": dict(by_name),
            "program_spans": totals}


def of(record):
    """The program-span reduction of ``record``'s traced window, or None
    where the record has no trace. Read from the trace directory that
    ``bench/run.py`` writes for the cell, once per record."""
    tr = record.get("trace")
    if not tr:
        return None
    if "program" not in tr:
        trace_dir = os.path.join(TRACE_ROOT, record["cell"]) if "cell" in record else ""
        tr["program"] = reduce(load(trace_dir)) if os.path.isdir(trace_dir) else None
    return tr["program"]


def idle_share(program: dict, *names) -> float:
    """Percent of the window in which the device idled with the host's
    innermost span one of ``names``."""
    idle = program["idle_by_program_span_s"]
    return 100.0 * sum(idle.get(n, 0.0) for n in names) / program["window_s"]
