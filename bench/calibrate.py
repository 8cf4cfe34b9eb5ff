"""Readings that set the limits of ``correct``, on the chip at the cell's size.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --variant program

Each seed runs the cell's set-up and first steps (no measured window)
and the comparison with the plain reference, in one process, and prints
one JSON line with the compared numbers. ``--variant``:

* ``program`` — the program as the configuration states it (the lower
  readings);
* ``control`` — the program's own next-lower weight precision, INT4
  (the upper readings);
* ``half_batch`` — a planted fault: the loss leaves out the second half
  of every batch and takes the mean over the rest;
* ``unchanged`` — a planted fault: the optimizer returns its state
  unchanged;
* ``altered`` — a planted fault: the serving engine hands the client
  another token than the one it chose.

Training cells read the first steps only; serving cells a short window
(``SERVE_SECONDS``) at the mix's own load, served to its end.

The benchmark's own runs never run these.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

CONTROL = {"quant": 4}
SERVE_SECONDS = 10.0


@contextlib.contextmanager
def half_batch():
    """The cached-step loss over the first half of each batch only."""
    import repro.kernels.cached_step as cs

    original = cs.cached_loss_parts

    def loss_parts(bp, ap, cfg, cached, *a, **k):
        labels = cached["labels"]
        keep = labels.shape[0] // 2
        cached = dict(cached, labels=labels.at[keep:].set(-100))
        return original(bp, ap, cfg, cached, *a, **k)

    cs.cached_loss_parts = loss_parts
    try:
        yield
    finally:
        cs.cached_loss_parts = original


@contextlib.contextmanager
def unchanged():
    """Every step returns the adapter and optimizer state it was given."""
    import repro.core.steps as st

    original = st.adamw_update
    st.adamw_update = lambda params, grads, state, **k: (params, state)
    try:
        yield
    finally:
        st.adamw_update = original


@contextlib.contextmanager
def altered():
    """Every token the engine produces reaches the client as the next id."""
    from repro.serve.engine import ServeEngine

    original = ServeEngine._accept_token

    def accept(self, req, tok):
        return original(self, req, (tok + 1) % self.cfg.vocab)

    ServeEngine._accept_token = accept
    try:
        yield
    finally:
        ServeEngine._accept_token = original


VARIANTS = {"program": ({}, None), "control": (CONTROL, None),
            "half_batch": ({}, half_batch), "unchanged": ({}, unchanged),
            "altered": ({}, altered)}


def reading(cell, seed: int, variant: str, seconds: float = SERVE_SECONDS) -> dict:
    from bench import serve, train

    override, fault = VARIANTS[variant]
    fault = fault() if fault else None
    if cell.traffic["kind"] == "serve":
        out = serve.run(cell, seed, seconds, t_start=0.0, override=override, fault=fault)
    else:
        out = train.run(cell, seed, 0.0, t_start=0.0, window=False, override=override,
                        fault=fault)
    nums = out["numbers"]
    return {"workload": cell.name, "variant": variant, "seed": seed,
            **{k: v for k, v in nums.items() if not k.startswith("_")}, "where": nums["_where"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--variant", choices=sorted(VARIANTS), default="program")
    args = ap.parse_args(argv)
    from bench import manifest
    from bench.run import configure_jax, devices_or_exit

    cell = manifest.cell(args.workload)
    configure_jax()
    devices_or_exit(cell.chips)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(reading(cell, seed, args.variant)), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    from bench.run import bootstrap

    bootstrap()
    sys.exit(main())
