"""Median host-clock wall of the benchmark's ``engine.step()`` calls in
the window (each ends in the engine's own host argmax, so the step's
device work is complete)."""

import numpy as np


def read(record):
    steps = [s["wall_s"] for s in record.get("engine_steps", []) if s["in_window"]]
    if not record.get("trace") or not steps:
        return None
    return 1e3 * float(np.median(steps))
