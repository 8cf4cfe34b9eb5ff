"""Share of the traced training window in which the device idles while
the host is in ``EdgeSession.step``'s own work, outside the activation
cache: the step's self time (``pac.step``), the cache lookup or
prefetcher pull outside the wait on its queue (``pac.step.lookup``), the
call of the jitted step (``pac.step.dispatch``) and ``float(loss)``
(``pac.step.sync``, where the host waits for the step's device work).
Extras: each of the four, as ``step``, ``lookup``, ``dispatch``,
``sync``."""

from bench import spans

PARTS = {"step": "pac.step", "lookup": "pac.step.lookup", "dispatch": "pac.step.dispatch",
         "sync": "pac.step.sync"}


def read(record):
    prog = spans.of(record)
    if prog is None or "pac.step" not in prog["program_spans"]:
        return None
    return (spans.idle_share(prog, *PARTS.values()),
            {k: spans.idle_share(prog, n) for k, n in PARTS.items()})
