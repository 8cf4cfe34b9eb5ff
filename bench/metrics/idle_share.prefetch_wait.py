"""Share of the traced cached window in which the device idles while the
step's thread waits on the activation cache's prefetcher (span
``pac.prefetch.wait``, around the blocking queue get). Extra:
``first_of_epoch``, the share from waits for an epoch's first batch
(``n == 0``: the prefetcher of each epoch starts cold)."""

from bench import spans

WAIT = "pac.prefetch.wait"


def read(record):
    prog = spans.of(record)
    if prog is None or WAIT not in prog["program_spans"]:
        return None
    first = prog["program_spans"][WAIT].get("first", {"idle_s": 0.0})
    return spans.idle_share(prog, WAIT), {
        "first_of_epoch": 100.0 * first["idle_s"] / prog["window_s"]}
