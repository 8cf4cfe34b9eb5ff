"""Driver of the training cells: epoch-1 capture and cached epochs.

One object, the program's ``EdgeSession`` with the benchmark's weights,
is built in set-up, driven through its first steps by the window's own
call (``EdgeSession.step``) on rows that all differ, and handed to the
window. The plain reference then follows those first steps.

* ``"phase": "capture"`` — every step is a cache miss: the frozen INT8
  backbone forward through the Pallas OpSet, taps emitted in storage
  form, the adapter step, and ``put_batch``. After each job's rows the
  harness clears the cache and the next job brings new row ids.
* ``"phase": "cached"`` — set-up captures one job, puts the adapter
  back to its initial state, and the window runs cached epochs under
  ``epoch_scope``: the prefetcher feeds the fused cached step and the
  backbone does not run.
"""

from __future__ import annotations

import contextlib
import gc

import jax
import numpy as np

from bench import compare, model, traffic
from bench.harness import TRACE_MAX_S, CompileCounter, now, peak_bytes, profiled, span
from bench.reference import Reference


def _batch(tokens: np.ndarray, ids: np.ndarray) -> dict:
    return {"tokens": tokens[:, :-1], "labels": tokens[:, 1:], "seq_ids": ids.astype(np.int32)}


def _job_batches(session, seed, tr, vocab, job):
    with span("bench.batch_gen"):
        tokens = traffic.job_tokens(seed, job, tr, vocab)
    ids = job * tr["rows_per_job"] + np.arange(tr["rows_per_job"])
    for i in range(0, tr["rows_per_job"], tr["batch"]):
        yield _batch(tokens[i:i + tr["batch"]], ids[i:i + tr["batch"]])


def capture_stream(session, seed, tr, vocab):
    """Jobs one after another; the cache is cleared between jobs."""
    job = 0
    while True:
        yield from _job_batches(session, seed, tr, vocab, job)
        with span("bench.cache_clear"):
            session.cache.clear()
        job += 1


def cached_stream(session, tokens, epoch0: int = 1):
    """Cached epochs over job 0's rows, in the session's epoch order."""
    epoch = epoch0
    while True:
        with session.epoch_scope(epoch) as from_cache:
            if not from_cache:
                raise RuntimeError(f"epoch {epoch} is not covered by the activation cache")
            for ids in session.pipe.epoch_order(epoch):
                yield _batch(tokens[ids], ids)
        epoch += 1


def run(cell, seed: int, seconds: float, *, t_start: float, trace_dir=None,
        window: bool = True, override=None, fault=None) -> dict:
    """One run of a training cell. ``window=False`` stops after the
    first steps and the comparison (the correctness readings);
    ``override`` changes RunSpec fields (the control: ``quant=4``);
    ``fault`` is a context manager planted around the whole run."""
    config, tr = cell.config, cell.traffic
    vocab = config["arch"]["vocab"]
    out = {"phase": tr["phase"]}
    marks = {"driver": now()}
    with (fault or contextlib.nullcontext()), CompileCounter() as compiles:
        session = model.open_session(config, tr, seed, marks, **(override or {}))
        start = jax.device_get(session.adapter)
        if tr["phase"] == "capture":
            stream = capture_stream(session, seed, tr, vocab)
        else:
            job0 = traffic.job_tokens(seed, 0, tr, vocab)
            for batch in _job_batches(session, seed, tr, vocab, 0):
                session.step(batch)
            out["cache_bytes"], out["cache_tokens"] = session.cache.nbytes, job0[:, 1:].size
            model.set_adapter(session, start)
            stream = cached_stream(session, job0)
            marks["captured"] = now()
        # the first steps: the window's own call, followed by the reference
        first, losses, mu1 = [], [], None
        for i in range(tr["first_steps"]):
            batch = next(stream)
            first.append((batch["tokens"], batch["labels"]))
            losses.append(session.step(batch).loss)
            if i == 0:
                mu1 = jax.device_get(session.opt["mu"])
        prog = {"losses": losses, "mu1": mu1, "start": start,
                "end": jax.device_get(session.adapter)}
        marks["first_steps"] = now()
        tokens_per_step = tr["batch"] * tr["seq"]
        if window:
            limit = min(seconds, TRACE_MAX_S) if trace_dir else seconds
            walls, window_losses = [], []
            out["setup_s"] = now() - t_start
            compiles.active = True
            with profiled(trace_dir), span("bench.window"):
                t0 = now()
                while True:
                    with span("bench.next_batch"):
                        batch = next(stream)
                    with span("bench.step"):
                        event = session.step(batch)
                    walls.append(event.wall_s)
                    window_losses.append(event.loss)
                    if now() - t0 >= limit:
                        break
                out["window_s"] = now() - t0
            compiles.active = False
            out.update(steps=len(walls), tokens=len(walls) * tokens_per_step,
                       step_walls=walls, attempted=len(walls),
                       failed=int(sum(not np.isfinite(x) for x in window_losses)),
                       e2e={"train_tokens_per_s": len(walls) * tokens_per_step / out["window_s"]})
        out["compiles_in_window"] = compiles.n
        out["info"] = {"setup_marks_s": {k: v - t_start for k, v in marks.items()}, "compile": compiles.summary()}
        out["peak_bytes"] = peak_bytes()
        stream.close()
        session.close()
        del session, stream
        gc.collect()
    t_ref = now()
    losses, g1, end, start = Reference(config, seed).train(first)
    out["info"]["reference_s"] = now() - t_ref
    out["numbers"] = compare.training_numbers(
        prog, {"losses": losses, "g1": g1, "start": start, "end": end},
        config["optimizer"]["b1"])
    return out
