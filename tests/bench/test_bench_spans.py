"""bench/spans.py and the readers of the program's spans, on a small
synthetic trace (times in ns)."""

import copy

import pytest

import benchcells  # noqa: F401  (puts the checkout's root on sys.path)
from bench import manifest, peaks, spans
from bench import trace as T

M = manifest.manifest()
NEW = ["idle_share.put_batch", "idle_share.prefetch_wait", "idle_share.step_host",
       "prefetch_load_ms"]
EARLIER = [m["name"] for m in M["per_layer"] if m["name"] not in NEW]

QMM = '%quant_matmul.54 = f32[4096,2048]{1,0} custom-call(f32[4096,2048]{1,0} %x)'
HEAD = ('%lmhead_ce_fwd.3 = (f32[4096,1]{1,0}) custom-call(f32[2048,92672]{1,0} %pad.0), '
        'custom_call_target="tpu_custom_call"')
# device 0 busy 1000..1300, 2100..3000, 4500..4600, 7050..9000 of the
# window 1000..11000; an op before the window that must not count
DEV0 = [("fusion.1", 1000, 300), (QMM, 2100, 900), ("copy.2", 4500, 100),
        (HEAD, 7050, 1950), ("fusion.0", 100, 500)]

# the step's thread: (name, start, duration, stats)
MAIN = [
    ("bench.window", 1000, 10000, {}),
    ("bench.next_batch", 1000, 500, {}),
    ("bench.step", 1500, 4500, {}),
    ("pac.step", 1600, 4300, {}),
    ("pac.step.lookup", 1700, 300, {}),
    ("pac.step.dispatch", 2000, 200, {}),
    ("pac.cache.put_batch", 2200, 2800, {}),
    ("pac.cache.fetch", 2300, 1700, {"nbytes": 300}),
    ("pac.cache.store", 4000, 900, {}),
    ("pac.step.sync", 5000, 800, {}),
    ("bench.step", 6000, 5000, {}),
    ("pac.step", 6100, 4800, {}),
    ("pac.step.lookup", 6200, 800, {}),
    ("pac.prefetch.wait", 6300, 600, {"n": 0}),
    ("pac.step.dispatch", 7000, 100, {}),
    ("pac.step.sync", 7100, 3700, {}),
    ("not.ours", 0, 20000, {}),
]
# the prefetcher's thread: it overlaps idle time and must take none
WORKER = [
    ("pac.prefetch.load", 0, 500, {}),        # before the window
    ("pac.prefetch.load", 1200, 600, {}),
    ("pac.prefetch.device_put", 1800, 100, {"nbytes": 500}),
    ("pac.prefetch.load", 6400, 200, {}),
    ("pac.prefetch.device_put", 6600, 200, {"nbytes": 500}),
]


def _line(name, events, program=True):
    kept = [e for e in events if program or not e[0].startswith(spans.PREFIX)]
    return {"name": name, "events": [(n, s, d) for n, s, d, _ in kept],
            "stats": {i: st for i, (n, _, _, st) in enumerate(kept)
                      if n.startswith(spans.PREFIX)}}


def _planes(program=True):
    host = {"name": "/host:CPU", "lines": [_line("python", MAIN, program),
                                           _line("python", WORKER, program)]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [("jit_step", 0, 20000)]},
        {"name": "XLA Ops", "events": list(DEV0)}]}
    return [host, dev]


def _record(program=True):
    cell = manifest.cell("internlm2-1.8b.capture", M)
    planes = _planes(program)
    tr = dict(T.reduce(planes), program=spans.reduce(planes))
    return {"cell": cell.name, "config": cell.config, "traffic": cell.traffic,
            "trace": tr, "peaks": peaks.peaks("TPU v5 lite"), "steps": 2, "tokens": 8192,
            "window_s": 1e-5, "cache_bytes": 4096, "cache_tokens": 64}


def test_idle_is_split_at_span_boundaries_into_self_time():
    red = spans.reduce(_planes())
    idle = red["idle_by_program_span_s"]
    want = {"bench.next_batch": 200, "bench.step": 400, "pac.step": 400,
            "pac.step.lookup": 500, "pac.step.dispatch": 150, "pac.cache.fetch": 1000,
            "pac.cache.store": 800, "pac.cache.put_batch": 100, "pac.step.sync": 2600,
            "pac.prefetch.wait": 600}
    assert idle == {k: pytest.approx(v / 1e9) for k, v in want.items()}
    busy = T.reduce(_planes())["busy_s"]
    assert sum(idle.values()) == pytest.approx(red["window_s"] - busy)
    assert sum(idle.values()) == pytest.approx(6750 / 1e9)


def test_worker_spans_take_no_idle_time():
    idle = spans.reduce(_planes())["idle_by_program_span_s"]
    assert not any(n.startswith("pac.prefetch.load") or n.startswith("pac.prefetch.device")
                   for n in idle)


def test_spans_crossing_the_window_edge_are_clipped_and_not_counted():
    # device 0 idles the whole window 1000..3000; pac.step opened before it
    main = [("bench.window", 1000, 2000, {}), ("pac.step", 500, 1000, {}),
            ("pac.step.sync", 2500, 1000, {})]
    planes = [{"name": "/host:CPU", "lines": [_line("python", main)]},
              {"name": "/device:TPU:0", "lines": [
                  {"name": "XLA Ops", "events": [("fusion.0", 0, 500)]}]}]
    red = spans.reduce(planes)
    assert red["idle_by_program_span_s"] == {
        "pac.step": pytest.approx(5e-7), "bench.window": pytest.approx(1e-6),
        "pac.step.sync": pytest.approx(5e-7)}
    assert set(red["program_spans"]) == {"pac.step.sync"}


def test_program_spans_are_counted_with_their_bytes():
    got = spans.reduce(_planes())["program_spans"]
    assert got["pac.step"] == {"count": 2, "s": pytest.approx(9.1e-6), "nbytes": 0}
    assert got["pac.cache.fetch"] == {"count": 1, "s": pytest.approx(1.7e-6), "nbytes": 300}
    assert got["pac.prefetch.load"] == {"count": 2, "s": pytest.approx(8e-7), "nbytes": 0}
    assert got["pac.prefetch.device_put"] == {"count": 2, "s": pytest.approx(3e-7),
                                              "nbytes": 1000}
    assert got["pac.prefetch.wait"]["first"] == {
        "count": 1, "s": pytest.approx(6e-7), "nbytes": 0, "idle_s": pytest.approx(6e-7)}


def test_new_readers_read_the_spans():
    record = _record()
    read = {n: manifest.reader(n)(record) for n in NEW}
    share, extra = read["idle_share.put_batch"]
    assert share == pytest.approx(19.0)
    assert extra == {"fetch": pytest.approx(10.0), "store": pytest.approx(8.0),
                     "d2h_gb_per_s": pytest.approx(300 / 1700), "d2h_gb_per_idle_s": 0.3}
    assert read["idle_share.prefetch_wait"] == (pytest.approx(6.0),
                                                {"first_of_epoch": pytest.approx(6.0)})
    share, extra = read["idle_share.step_host"]
    assert share == pytest.approx(36.5)
    assert extra == {"step": pytest.approx(4.0), "lookup": pytest.approx(5.0),
                     "dispatch": pytest.approx(1.5), "sync": pytest.approx(26.0)}
    ms, extra = read["prefetch_load_ms"]
    assert ms == pytest.approx(5.5e-4)
    assert extra == {"load_ms": pytest.approx(4e-4), "device_put_ms": pytest.approx(1.5e-4),
                     "h2d_bytes_per_batch": 500}


@pytest.mark.parametrize("name", EARLIER)
def test_earlier_readers_read_the_same_with_and_without_program_spans(name):
    with_spans, without = _record(), _record(program=False)
    assert without["trace"]["program"]["program_spans"] == {}
    got = manifest.reader(name)(with_spans)
    assert got == manifest.reader(name)(without)
    assert got is not None


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_without_program_spans(name, tmp_path, monkeypatch):
    assert manifest.reader(name)(_record(program=False)) is None
    # nor where the cell's trace directory is missing
    monkeypatch.setattr(spans, "TRACE_ROOT", str(tmp_path))
    record = _record()
    del record["trace"]["program"]
    assert manifest.reader(name)(copy.deepcopy(record)) is None
