"""Smoke run of the PAC main path on a TPU, at internlm2-1.8b's full width.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # hybrid dp2×pp2 against one chip

One process, through the public entry points, on random weights drawn
from ``--seed``:

* fine-tuning — ``EdgeSession(RunSpec(...)).run()``: an epoch-1 capture
  (frozen INT8 backbone forward through the Pallas OpSet, taps emitted
  as int8 cache entries) and one cached epoch served from the
  activation cache by the fused Pallas step;
* serving — ``session.serving_engine()``: a few requests over two
  adapters, Pallas decode over an int8 paged KV pool.

It checks that every loss is finite, that the cached epoch ran from the
cache, that both training steps hold their Pallas kernels as
``tpu_custom_call`` (compiled for the chip, not interpreted), and that
the first epoch-1 loss agrees with the ``kernels="ref"`` step on the
same weights and batch. ``--four-chips`` runs only the dp2×pp2 session
and the one-chip session it is compared with, step by step.

Times and memory printed on the way are informational. The last line of
standard output is one JSON object naming the device. Without a TPU the
script exits non-zero before running anything.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# test_int8_tap_loss_close_to_ref: int8 taps vs the f32-tap ref step
REF_LOSS_TOL = 5e-2
# test_train_distributed: dp×pp vs single-device losses, fp32
DIST_LOSS_TOL = 1e-4


def require(ok: bool, what) -> None:
    """A smoke check: raises (not ``assert``, which ``-O`` strips)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def smoke_spec(seed: int):
    from repro.runtime import RunSpec

    return RunSpec(arch="internlm2-1.8b", reduced=False, quant=8,
                   kernels="pallas", cache_compress="int8", batch=4, seq=512,
                   epochs=2, steps_per_epoch=2, seed=seed)


class _Probe:
    """RunHooks that keep what the checks need: every StepEvent, the
    initial adapter/optimizer state and first batch (for the ref step),
    and each epoch's first step lowered and compiled once (its text and
    compile time)."""

    def __init__(self):
        from repro.runtime import RunHooks

        probe = self

        class Hook(RunHooks):
            def on_epoch_start(self, s, epoch):
                batch = next(s.pipe.epoch(epoch))
                if epoch == 0:
                    probe.ap0, probe.opt0 = s.adapter, s.opt
                    probe.batch0 = {k: v for k, v in batch.items()
                                    if k != "seq_ids"}
                lowered = s.lower_step(batch)
                t0 = time.perf_counter()
                lowered.compile()
                probe.compile_s[epoch] = time.perf_counter() - t0
                probe.text[epoch] = lowered.as_text()

            def on_step(self, s, event):
                probe.events.append(event)
                print(f"epoch {event.epoch} step {event.index}: "
                      f"loss={event.loss!r} mode={event.mode} "
                      f"cache_hit={event.cache_hit} wall={event.wall_s:.3f}s",
                      flush=True)

        self.hook = Hook()
        self.events, self.compile_s, self.text = [], {}, {}


def train(spec, check_placement: bool = False):
    """Run ``spec`` through EdgeSession; returns (session, probe)."""
    import jax

    from repro.runtime import EdgeSession

    probe = _Probe()
    session = EdgeSession(spec, log=lambda m: print(m, flush=True))
    reports = session.run(hooks=[probe.hook])
    losses = [e.loss for e in probe.events]
    require(len(losses) == spec.epochs * spec.steps_per_epoch, losses)
    require(all(math.isfinite(x) for x in losses), losses)
    first = [e for e in probe.events if e.epoch == 0]
    cached = [e for e in probe.events if e.epoch > 0]
    require(not any(e.cache_hit for e in first), "epoch 1 must run the backbone")
    require(cached and all(e.cache_hit for e in cached)
            and all(r.used_cache for r in reports[1:]),
            "cached epochs must be served from the activation cache")
    for epoch, text in sorted(probe.text.items()):
        require("tpu_custom_call" in text,
                f"epoch {epoch}'s step holds no compiled Pallas kernel")
        print(f"epoch {epoch} step: tpu_custom_call in lowered text; "
              f"compile {probe.compile_s[epoch]:.1f}s", flush=True)
    if check_placement:
        # make_mesh orders the devices along the chips' torus, not by id
        mesh_devs = set(session.mesh.devices.flat)
        require(session.mesh.devices.size == 4
                and mesh_devs == set(jax.devices()[:4]),
                session.mesh.devices)
        for name in ("backbone", "adapter", "opt"):
            for leaf in jax.tree.leaves(getattr(session, name)):
                require(leaf.sharding.device_set == mesh_devs,
                        (name, leaf.sharding))
        print("placement: mesh spans devices 0-3; backbone, adapter and "
              "optimizer state resident on all four", flush=True)
    return session, probe


def check_ref_step(session, probe):
    """The first epoch-1 step again, on the ref OpSet (dense f32, f32
    taps), from the same initial state and batch."""
    import functools

    import jax

    from repro.core import steps

    spec = session.spec
    ref_step = jax.jit(functools.partial(
        steps.pac_train_step, cfg=session.cfg, r=spec.r, lr=spec.lr,
        kernel_impl="ref"))
    loss_ref = float(ref_step(session.backbone, probe.ap0, probe.opt0,
                              probe.batch0)[0])
    loss_pal = probe.events[0].loss
    print(f"first step: pallas loss={loss_pal!r} ref loss={loss_ref!r} "
          f"|diff|={abs(loss_pal - loss_ref)!r} (tol {REF_LOSS_TOL})",
          flush=True)
    require(abs(loss_pal - loss_ref) < REF_LOSS_TOL, (loss_pal, loss_ref))


def serve(session, probe, n_requests: int = 4, new_tokens: int = 8,
          prompt_len: int = 16):
    """A few requests over two adapters: the one just trained and the
    initial one."""
    engine = session.serving_engine(
        adapters={"tuned": session.adapter, "initial": probe.ap0},
        kv_policy="int8", max_len=64, max_batch=n_requests)
    prompts = session.corpus.tokens[:n_requests, :prompt_len]
    handles = [
        engine.submit(p.tolist(), adapter=("tuned", "initial")[i % 2],
                      max_new_tokens=new_tokens)
        for i, p in enumerate(prompts)
    ]
    t0 = time.perf_counter()
    engine.drain()
    wall = time.perf_counter() - t0
    vocab = session.cfg.vocab
    out = [h.result(timeout=0) for h in handles]
    for toks in out:
        require(len(toks) == new_tokens
                and all(0 <= t < vocab for t in toks), toks)
    n = sum(len(t) for t in out)
    print(f"serving: {n_requests} requests, 2 adapters, {n} tokens produced "
          f"in {wall:.3f}s (compile included), first request {out[0]}",
          flush=True)


def one_chip(seed: int) -> None:
    session, probe = train(smoke_spec(seed))
    check_ref_step(session, probe)
    serve(session, probe)


def four_chips(seed: int) -> None:
    import jax

    require(len(jax.devices()) >= 4, f"--four-chips needs 4 devices: {jax.devices()}")
    spec = smoke_spec(seed)
    # the one-chip run first; its session is dropped before the next opens
    ref = [e.loss for e in train(spec)[1].events]
    _, probe = train(spec.replace(dp=2, stages=2), check_placement=True)
    dist = [e.loss for e in probe.events]
    for i, (a, b) in enumerate(zip(ref, dist)):
        print(f"step {i}: one chip {a!r} dp2xpp2 {b!r} |diff|={abs(a - b)!r}",
              flush=True)
    require(max(abs(a - b) for a, b in zip(ref, dist)) < DIST_LOSS_TOL, (ref, dist))
    modes = [e.mode for e in probe.events]
    require(modes == ["hybrid dp2xpp2"] * spec.steps_per_epoch
            + ["cached pure-dp"] * spec.steps_per_epoch, modes)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the dp2×pp2 session against one chip, only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro import compat

    cache_dir = compat.enable_compilation_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__}",
          flush=True)
    print(f"compile cache: {cache_dir} ({len(os.listdir(cache_dir))} entries "
          f"at start)", flush=True)
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    peak = dev.memory_stats().get("peak_bytes_in_use")
    print(f"device 0 peak_bytes_in_use={peak}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
