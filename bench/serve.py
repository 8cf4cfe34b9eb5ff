"""Driver of the serving cells: multi-tenant chat on the paged engine.

The program's ``ServeEngine`` (``EdgeSession.serving_engine``) serves a
bank of adapters made from the seed over the benchmark's backbone. The
harness is the open-loop client: one thread submits each request when
it is due and drives ``engine.step()`` itself (``start()`` is not
called), timing every token as the step that made it returns.

Set-up warms, through ``submit``/``step`` alone, every shape the mix
can reach: each prefill (bucket, padded length) pair and each decode
bucket. The window then offers the mix's requests at their due times
for ``--seconds``; requests in flight when it closes are served to the
end (up to ``drain_s`` more), and one that never finishes is failed.
Once the engine is freed, a sample of finished requests drawn from the
seed, the longest among them, is compared with the plain reference.
"""

from __future__ import annotations

import contextlib
import gc
import inspect
import time

import jax
import numpy as np

from bench import manifest, model, traffic
from bench import weights as W
from bench.harness import TRACE_MAX_S, CompileCounter, now, peak_bytes, profiled, span
from bench.reference import Reference

# the session's own training fields, which serving never reads
_SESSION = {"batch": 1, "seq": 16, "rows_per_job": 1, "cache_budget_mb": 64}
REF_BUCKET = 128  # the reference pads each sequence to a multiple of this


def _pow2_up_to(lo: int, hi: int) -> list:
    out, b = [], 1
    while b < hi:
        if b >= lo:
            out.append(b)
        b *= 2
    return out + [hi]


def adapter_maker(config: dict):
    """Jitted: the bank's adapter from its key (``STREAM_BANK + i``)."""
    side = manifest.side(config)
    return jax.jit(lambda k: W.make_adapter(k, config["arch"], config["adapter"], side))


def make_bank(config: dict, seed: int, n: int) -> dict:
    make = adapter_maker(config)
    return {f"a{i}": make(W.seed_key(seed, W.STREAM_BANK + i)) for i in range(n)}


def check_served_path(config: dict) -> None:
    """Refuse an architecture whose reference has no served path."""
    mod = manifest.arch_module(config)
    if "prompt_len" not in inspect.signature(mod.reference_layer).parameters:
        raise ValueError(f"architecture {config['architecture']!r} gives no served path "
                         f"(reference_layer takes no prompt_len): it cannot be served")


def warm_up(engine, eng: dict, tr: dict) -> None:
    """Every prefill (bucket, padded length) and decode bucket the mix
    can reach, and the retirement of a request that is not the last row
    (its row moves), through the engine's public calls."""
    buckets = _pow2_up_to(1, eng["max_batch"])
    lengths = _pow2_up_to(tr["prompt_len"]["min"], tr["prompt_len"]["max"])
    for b in buckets:
        for s in lengths:
            for _ in range(b):
                engine.submit([1] * s, adapter="a0", max_new_tokens=1)
            engine.drain()
        for j in range(b):
            engine.submit([1] * lengths[0], adapter="a0", max_new_tokens=2 + j % 2)
        engine.drain()


class _Client:
    """Per-request bookkeeping of the open-loop client."""

    def __init__(self, req: dict, handle, t_due: float, t_sent: float):
        self.req, self.handle = req, handle
        self.t_due, self.t_sent = t_due, t_sent
        self.times: list = []

    def poll(self, t: float) -> tuple:
        """(prompts prefilled, decode contexts) of the tokens that reached
        the client by ``t``: a first token comes from the prefill of the
        prompt; the k-th after it from a decode over ``len(prompt) + k``
        positions."""
        n_before, n = len(self.times), len(self.handle._generated)
        self.times.extend([t] * (n - n_before))
        P = len(self.req["prompt"])
        prefill = [P] if n_before == 0 and n > 0 else []
        return prefill, [P + k for k in range(max(n_before, 1), n)]


def run(cell, seed: int, seconds: float, *, t_start: float, trace_dir=None,
        override=None, fault=None) -> dict:
    config, tr = cell.config, cell.traffic
    check_served_path(config)
    eng, vocab = tr["engine"], config["arch"]["vocab"]
    limit = min(seconds, TRACE_MAX_S) if trace_dir else seconds
    reqs = traffic.requests(seed, tr, vocab, int(tr["rate_per_s"] * max(limit, 1) * 2) + 64)
    out = {}
    with (fault or contextlib.nullcontext()), CompileCounter() as compiles:
        session = model.open_session(config, _SESSION, seed, **(override or {}))
        bank = make_bank(config, seed, tr["n_adapters"])
        engine = session.serving_engine(
            adapters=bank, kv_policy=eng["kv_policy"], max_batch=eng["max_batch"],
            max_len=eng["max_len"], page_size=eng["page_size"])
        del bank
        warm_up(engine, eng, tr)
        traces_before = engine.n_traces
        out["setup_s"] = now() - t_start
        clients, steps = [], []
        compiles.active = True
        with profiled(trace_dir), span("bench.window"):
            t0 = now()
            i = 0
            while True:
                t = now() - t0
                while i < len(reqs) and reqs[i]["due_s"] <= t and reqs[i]["due_s"] < limit:
                    r = reqs[i]
                    with span("bench.submit"):
                        h = engine.submit(r["prompt"], adapter=f"a{r['adapter']}",
                                          max_new_tokens=r["max_new"])
                    clients.append(_Client(r, h, t0 + r["due_s"], now()))
                    i += 1
                open_ = [c for c in clients if not c.handle.done]
                closed = t >= limit or i == len(reqs) or reqs[i]["due_s"] >= limit
                if not open_:
                    if closed:
                        break
                    with span("bench.wait"):
                        time.sleep(max(0.0, min(reqs[i]["due_s"] - t, 0.002)))
                    continue
                if t >= limit + tr["drain_s"]:
                    break
                ts = now()
                with span("bench.engine_step"):
                    engine.step()
                te = now()
                prefill, ctx = [], []
                for c in open_:
                    p, k = c.poll(te)
                    prefill += p
                    ctx += k
                steps.append({"wall_s": te - ts, "in_window": ts - t0 < limit,
                              "prefill": prefill, "decode_ctx": ctx,
                              "waiting": sum(not c.times for c in open_)})
            out["window_s"] = limit
            out["elapsed_s"] = now() - t0
        compiles.active = False
        out["compiles_in_window"] = compiles.n + engine.n_traces - traces_before
        out["peak_bytes"] = peak_bytes()
        served = [(c.req, list(c.handle._generated)) for c in clients if c.handle.done]
        del engine, session
        gc.collect()
    _client_metrics(out, clients, steps)
    out["numbers"] = served_numbers(config, seed, tr, served) if served else {
        "served_logit_gap": float("inf"), "_where": {}}
    return out


def _client_metrics(out: dict, clients: list, steps: list) -> None:
    done = [c for c in clients if c.handle.done and len(c.times) == c.req["max_new"]]
    ttft = [(c.times[0] - c.t_due) * 1e3 for c in done]
    itl = [(b - a) * 1e3 for c in done for a, b in zip(c.times, c.times[1:])]
    out.update(
        steps=len(clients), attempted=len(clients), failed=len(clients) - len(done),
        ttft_ms=ttft, itl_ms=itl, engine_steps=steps,
        tokens=sum(len(c.times) for c in done),
        e2e={"ttft_p95_ms": percentile(ttft, 95), "itl_p95_ms": percentile(itl, 95)},
        info={"generator_late_max_s": max((c.t_sent - c.t_due for c in clients), default=0.0),
              "requests_done": len(done), "ttft_p50_ms": percentile(ttft, 50),
              "itl_p50_ms": percentile(itl, 50), "drain_s": out["elapsed_s"] - out["window_s"]})


def percentile(x: list, q: float) -> float:
    return float(np.percentile(np.asarray(x, np.float64), q)) if x else float("inf")


def sample(seed: int, served: list, n: int) -> list:
    """The longest finished request, and ``n - 1`` more drawn from the seed."""
    longest = max(range(len(served)), key=lambda j: len(served[j][0]["prompt"]) + len(served[j][1]))
    rest = [j for j in range(len(served)) if j != longest]
    pick = traffic.rng(seed, traffic.STREAM_REQUESTS, 1).permutation(rest)[: n - 1]
    return [served[j] for j in [longest, *pick]]


def served_numbers(config: dict, seed: int, tr: dict, served: list) -> dict:
    """``served_logit_gap``: the widest gap by which a served token's
    reference logit lies below the reference's best at its position."""
    ref = Reference(config, seed)
    head = ref.head()
    adapters, worst, n_tok = {}, 0.0, 0
    make = adapter_maker(config)
    for req, toks in sample(seed, served, tr["sample_requests"]):
        a = req["adapter"]
        if a not in adapters:
            adapters[a] = make(W.seed_key(seed, W.STREAM_BANK + a))
        P, seq = len(req["prompt"]), np.concatenate([req["prompt"], toks[:-1]]).astype(np.int32)
        padded = np.zeros(-(-len(seq) // REF_BUCKET) * REF_BUCKET, np.int32)
        padded[: len(seq)] = seq
        logits = np.asarray(ref.served_logits(adapters[a], padded, P, head))[P - 1: len(seq)]
        gaps = logits.max(axis=-1) - logits[np.arange(len(toks)), toks]
        worst, n_tok = max(worst, float(gaps.max())), n_tok + len(toks)
    return {"served_logit_gap": worst, "_where": {"served_tokens_compared": n_tok}}
