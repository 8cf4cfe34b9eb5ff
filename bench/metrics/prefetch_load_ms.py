"""Mean host time per batch on the activation cache prefetcher's worker
thread: assembling the batch from the cache (``pac.prefetch.load``) plus
enqueueing its host-to-device copy (``pac.prefetch.device_put``; the
copy itself runs on after the call returns). Spans that start in the
traced window. Extras: ``load_ms`` and ``device_put_ms``, each part's
mean; ``h2d_bytes_per_batch``, the bytes handed to ``device_put`` per
batch."""

from bench import spans


def read(record):
    prog = spans.of(record)
    if prog is None or "pac.prefetch.load" not in prog["program_spans"]:
        return None
    load = prog["program_spans"]["pac.prefetch.load"]
    put = prog["program_spans"].get("pac.prefetch.device_put", {"count": 0, "s": 0.0, "nbytes": 0})
    n = load["count"]
    return 1e3 * (load["s"] + put["s"]) / n, {
        "load_ms": 1e3 * load["s"] / n,
        "device_put_ms": 1e3 * put["s"] / n,
        "h2d_bytes_per_batch": put["nbytes"] / put["count"] if put["count"] else 0.0,
    }
