"""Run one cell of the on-chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on the machine that holds the chips the
cell asks for. Refuses to run (exit 2, no result) without a TPU or with
fewer chips than the cell needs. Set-up makes the weights and inputs
from ``--seed``, warms every shape the cell uses, then measures for
``--seconds``; the plain reference then decides ``correct``. The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device`` and, traced,
``breakdown``; its last key, ``checks``, holds each compared number
beside its limit, which also end standard error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

T_START = __import__("time").perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bootstrap() -> None:
    """For a process started as ``python3 bench/<script>.py``: the
    checkout's root, not bench/, leads sys.path (bench/trace.py must not
    shadow the standard library's ``trace``), the program's ``src`` is
    importable, and JAX's persistent compilation cache sits at a fixed
    path inside the checkout."""
    if os.path.abspath(sys.path[0]) == os.path.join(ROOT, "bench"):
        sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_jax() -> None:
    """Cache every compiled program, in the directory ``bootstrap`` set,
    with no size limit: a cell compiles a fixed set of programs, and a
    limit turns on eviction, whose bookkeeping fails every write once one
    entry in the directory lacks its access-time file."""
    import jax

    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)


def devices_or_exit(chips: int):
    """The local TPU devices, or exit 2 with no result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform!r} device(s). Nothing was run.", file=sys.stderr)
        sys.exit(2)
    return devs[:chips]


def run_cell(cell, seed: int, seconds: float, trace: bool, devs, t_start: float) -> dict:
    """The result object of one run (everything after the device check)."""
    from bench import compare, peaks, serve, train

    drivers = {"train": train.run, "serve": serve.run}
    driver = drivers[cell.traffic["kind"]]
    trace_dir = os.path.join(ROOT, ".bench_trace", cell.name) if trace else None
    out = driver(cell, seed, seconds, t_start=t_start, trace_dir=trace_dir)
    correct, checks = compare.judge(out["numbers"], cell.limits)
    correct &= out["failed"] == 0
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": out["peak_bytes"]}
    result = {"correct": bool(correct), "attempted": out["attempted"], "failed": out["failed"]}
    if trace:
        from bench import manifest, trace as tr

        reduced = tr.reduce(tr.load(trace_dir))
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        record = dict(out, cell=cell.name, config=cell.config, traffic=cell.traffic,
                      trace=reduced, peaks=peaks.peaks(dev.device_kind))
        metrics, not_read = {}, []
        for m in cell.per_layer:
            got = manifest.reader(m["name"])(record)
            if got is None:
                not_read.append(m["name"])
                continue
            value, extra = got if isinstance(got, tuple) else (got, {})
            metrics[m["name"]] = {"value": value, "unit": m["unit"], **extra}
        if not_read:
            # a metric the cell lists that found nothing: name it, since
            # its kernel or counter is then off this cell's path
            print(f"bench: found nothing to read for {not_read} in the trace of "
                  f"{cell.name}", file=sys.stderr)
        out.setdefault("info", {})["not_read"] = not_read
        result["breakdown"] = tr.breakdown(reduced)
        out.setdefault("info", {})["idle_by_span_s"] = reduced["idle_by_span_s"]
    else:
        e2e = dict(out["e2e"], setup_s=out["setup_s"], peak_hbm_gib=out["peak_bytes"] / 2 ** 30)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result.update(metrics=metrics, device=device,
                  info={"compiles_in_window": out["compiles_in_window"],
                        "window_s": out["window_s"], "where": out["numbers"]["_where"],
                        **out.get("info", {})},
                  checks=checks)
    return result


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import manifest

    cell = manifest.cell(args.workload)
    configure_jax()
    devs = devices_or_exit(cell.chips)
    print_result(run_cell(cell, args.seed, args.seconds, bool(args.trace), devs, T_START))
    return 0


if __name__ == "__main__":
    bootstrap()
    sys.exit(main())
