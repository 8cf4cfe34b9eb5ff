"""The program's host spans (``pac.*``) in a profiler trace.

A tiny session runs two capture steps and one cached epoch under the
profiler; the spans must all be there, nested as documented, the
prefetcher's on its own thread, with the byte counts the cache keeps;
and tracing must not change a result. Each capture batch is fetched
behind the next step: the first inside the second step's
``pac.cache.put_batch``, the last at the first read after the capture
(the cache's byte count), under ``pac.cache.drain``.
"""

import os
import sys

import jax
import numpy as np

from repro.runtime import EdgeSession, RunSpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import spans  # noqa: E402

STEP_SPANS = ["pac.step", "pac.step.lookup", "pac.step.dispatch", "pac.step.sync",
              "pac.cache.put_batch", "pac.cache.fetch", "pac.cache.store",
              "pac.prefetch.wait"]
BARRIER_SPANS = ["pac.cache.drain"]
WORKER_SPANS = ["pac.prefetch.load", "pac.prefetch.device_put"]


def _run(trace_dir=None):
    """Two capture steps, then one cached epoch of two; the losses, the
    adapter after them and the cache's byte count after the capture."""
    spec = RunSpec(arch="internlm2-1.8b", reduced=True, epochs=2, steps_per_epoch=2,
                   batch=2, seq=16, r=4, lr=1e-3, kernels="ref")
    losses = []
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    try:
        with EdgeSession(spec) as s:
            for epoch in range(spec.epochs):
                with s.epoch_scope(epoch):
                    for i, batch in enumerate(s.pipe.epoch(epoch)):
                        losses.append(s.step(batch, epoch=epoch, index=i).loss)
                if epoch == 0:
                    captured = s.cache.nbytes
            adapter = jax.device_get(s.adapter)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    return losses, adapter, captured


def _spans(planes):
    """{name: [(start, end, stats, line)]} of every pac.* event."""
    out = {}
    for p in planes:
        for li, ln in enumerate(p["lines"]):
            for j, (n, s, d) in enumerate(ln["events"]):
                if n.startswith("pac."):
                    out.setdefault(n, []).append(
                        (s, s + d, ln["stats"].get(j, {}), (p["name"], li)))
    return out


def _by_start(events):
    return sorted(events, key=lambda e: e[0])


def _inside(inner, outer):
    return any(o[0] <= inner[0] and inner[1] <= o[1] and o[3] == inner[3] for o in outer)


def test_spans_nest_and_count_without_changing_results(tmp_path):
    losses, adapter, captured = _run(str(tmp_path))
    got = _spans(spans.load(str(tmp_path)))
    assert set(got) == set(STEP_SPANS + BARRIER_SPANS + WORKER_SPANS)

    steps = got["pac.step"]
    assert len(steps) == 4
    for child in ["pac.step.lookup", "pac.step.dispatch", "pac.step.sync",
                  "pac.cache.put_batch"]:
        assert all(_inside(c, steps) for c in got[child]), child
    completing = got["pac.cache.put_batch"] + got["pac.cache.drain"]
    for part in ["pac.cache.fetch", "pac.cache.store"]:
        assert all(_inside(c, completing) for c in got[part]), part
    assert all(_inside(w, got["pac.step.lookup"]) for w in got["pac.prefetch.wait"])

    step_lines = {line for *_, line in steps + got["pac.cache.drain"]}
    worker_lines = {line for name in WORKER_SPANS for *_, line in got[name]}
    assert len(step_lines) == 1 and not step_lines & worker_lines

    assert len(got["pac.cache.put_batch"]) == 2
    fetches = _by_start(got["pac.cache.fetch"])
    fetched = [st["nbytes"] for *_, st, _ in fetches]
    assert len(fetched) == 2 and sum(fetched) == captured
    assert len(got["pac.cache.store"]) == 2
    # the first batch lands in the second step's put_batch, the last at
    # the cache.nbytes barrier, which carries the bytes it fetched
    first, last = fetches
    second_put = _by_start(got["pac.cache.put_batch"])[1]
    assert _inside(first, [second_put]) and _inside(first, [_by_start(steps)[1]])
    (drain,) = got["pac.cache.drain"]
    assert _inside(last, [drain]) and not _inside(last, steps)
    assert drain[2]["nbytes"] == fetched[1]
    assert sorted(st["n"] for *_, st, _ in got["pac.prefetch.wait"]) == [0, 1]
    assert len(got["pac.prefetch.load"]) == 2
    assert [st["nbytes"] for *_, st, _ in _by_start(got["pac.prefetch.device_put"])] == fetched

    plain_losses, plain_adapter, plain_captured = _run()
    assert losses == plain_losses and captured == plain_captured
    for a, b in zip(jax.tree.leaves(adapter), jax.tree.leaves(plain_adapter)):
        np.testing.assert_array_equal(a, b)
