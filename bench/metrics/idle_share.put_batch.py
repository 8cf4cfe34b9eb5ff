"""Share of the traced capture window in which the device idles while the
host is in ``ActivationCache.put_batch`` (span ``pac.cache.put_batch``)
or its parts: ``pac.cache.fetch``, the device-to-host copy of the step's
storage-form taps, and ``pac.cache.store``, slicing them into
per-sequence entries. Extras: ``fetch`` and ``store``, each part's share
of the window; ``d2h_gb_per_s``, the fetched bytes over the fetch spans'
summed duration (span time, which includes waiting for the step's device
work to end); ``d2h_gb_per_idle_s``, the same bytes over the device's
idle time inside the fetch spans."""

from bench import spans


def read(record):
    prog = spans.of(record)
    if prog is None or "pac.cache.put_batch" not in prog["program_spans"]:
        return None
    fetch = prog["program_spans"].get("pac.cache.fetch", {"nbytes": 0, "s": 0.0})
    fetch_idle = prog["idle_by_program_span_s"].get("pac.cache.fetch", 0.0)
    share = spans.idle_share(prog, "pac.cache.put_batch", "pac.cache.fetch", "pac.cache.store")
    return share, {
        "fetch": spans.idle_share(prog, "pac.cache.fetch"),
        "store": spans.idle_share(prog, "pac.cache.store"),
        "d2h_gb_per_s": fetch["nbytes"] / fetch["s"] / 1e9 if fetch["s"] else 0.0,
        "d2h_gb_per_idle_s": fetch["nbytes"] / fetch_idle / 1e9 if fetch_idle else 0.0,
    }
