"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` the profiler wrote (with nothing but
JAX) into plain lists; ``reduce`` works on those lists only, so the
tests can hand it a small synthetic trace:

* the traced window is the host span ``bench.window``;
* device busy time is the union of the op intervals on each TPU
  plane's ``XLA Ops`` line inside the window, averaged over the chips;
* each op's device time is the sum of its events' durations. On a TPU
  an op's event name is its whole HLO instruction (``%quant_matmul.54 =
  f32[...] custom-call(...), custom_call_target="tpu_custom_call"``);
  the kernel readers find their kernel by its instruction name or by its
  operands. Ops nest (a ``while`` holds the ops of its body), so op
  times overlap; busy time does not;
* each idle gap of the device is attributed to the innermost benchmark
  host span (``bench.*``) covering its midpoint, or to the window
  itself where the host was in none.
"""

from __future__ import annotations

import collections
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def load(trace_dir: str) -> list:
    """[{"name", "lines": [{"name", "events": [(name, start_ns, dur_ns)]}]}]."""
    from jax.profiler import ProfileData

    from bench.harness import trace_file

    data = ProfileData.from_file(trace_file(trace_dir))
    return [{"name": p.name,
             "lines": [{"name": ln.name,
                        "events": [(e.name, float(e.start_ns), float(e.duration_ns))
                                   for e in ln.events]} for ln in p.lines]}
            for p in data.planes]


def op_name(op: str) -> str:
    """The instruction name of an op's event (``quant_matmul.54``)."""
    return op.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(intervals, w0, w1):
    return [(max(a, w0), min(b, w1)) for a, b in intervals if b > w0 and a < w1]


def host_spans(planes) -> list:
    """(name, start_ns, end_ns) of every benchmark span on a host plane."""
    return [(n, s, s + d) for p in planes if p["name"].startswith("/host:")
            for ln in p["lines"] for n, s, d in ln["events"] if n.startswith(SPAN_PREFIX)]


def device_ops(planes) -> list:
    """One list of (name, start_ns, end_ns) per TPU plane."""
    out = []
    for p in planes:
        if not DEVICE_PLANE.match(p["name"]):
            continue
        for ln in p["lines"]:
            if ln["name"] == OPS_LINE:
                out.append([(n, s, s + d) for n, s, d in ln["events"]])
    if not out:
        raise ValueError("no TPU plane with an 'XLA Ops' line in the trace: "
                         f"{[p['name'] for p in planes]}")
    return out


def reduce(planes) -> dict:
    spans = host_spans(planes)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = windows[0]
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN and e > w0 and s < w1]
    per_device = device_ops(planes)
    busy, op_time, op_count = [], collections.Counter(), collections.Counter()
    gaps = []
    for i, ops in enumerate(per_device):
        in_window = [(n, s, e) for n, s, e in ops if e > w0 and s < w1]
        for n, s, e in in_window:
            op_time[n] += (min(e, w1) - max(s, w0)) / 1e9
            op_count[n] += 1
        merged = _union(_clip([(s, e) for _, s, e in in_window], w0, w1))
        busy.append(sum(b - a for a, b in merged) / 1e9)
        if i == 0:
            edges = [w0] + [x for ab in merged for x in ab] + [w1]
            gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    n_dev = len(per_device)
    idle_by_span, longest = collections.Counter(), []
    for a, b in gaps:
        mid = (a + b) / 2
        covering = [(e - s, n) for n, s, e in inner if s <= mid <= e]
        name = min(covering)[1] if covering else WINDOW_SPAN
        idle_by_span[name] += (b - a) / 1e9
        longest.append((name, (b - a) / 1e9))
    longest.sort(key=lambda x: -x[1])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / n_dev,
        "n_devices": n_dev,
        "op_time_s": {k: v / n_dev for k, v in op_time.items()},
        "op_count": {k: v / n_dev for k, v in op_count.items()},
        "idle_by_span_s": dict(idle_by_span),
        "longest_gaps": longest[:10],
    }


def kernel_time(reduced: dict, match) -> tuple:
    """(seconds, calls) of the device ops that ``match``: a pattern over
    the instruction name, or a predicate over the whole HLO text."""
    if isinstance(match, str):
        rx = re.compile(match)
        match = lambda op: rx.search(op_name(op)) is not None  # noqa: E731
    hits = [k for k in reduced["op_time_s"] if match(k)]
    return (sum(reduced["op_time_s"][k] for k in hits),
            sum(reduced["op_count"][k] for k in hits))


def breakdown(reduced: dict) -> dict:
    ops = sorted(reduced["op_time_s"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[op_name(k), v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in reduced["longest_gaps"]]}
