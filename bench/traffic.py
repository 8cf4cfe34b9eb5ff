"""The one traffic generator: a mix's data file in, the inputs out.

Everything is drawn from ``--seed`` with numpy; the program receives
only what is generated here.

* Training mixes (``"kind": "train"``) are streams of personal
  fine-tune jobs: job ``j`` is ``rows_per_job`` rows of ``seq + 1``
  token ids, uniform over the vocabulary, every row different.
* Serving mixes (``"kind": "serve"``) are open-loop request schedules:
  Poisson arrivals at ``rate_per_s``, lognormal prompt and output
  lengths (median and sigma, clipped), and a Zipf popularity over
  ``n_adapters`` adapters.
"""

from __future__ import annotations

import numpy as np

STREAM_JOBS = 11
STREAM_REQUESTS = 12


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), *map(int, stream)]))


def job_tokens(seed: int, job: int, traffic: dict, vocab: int) -> np.ndarray:
    """(rows_per_job, seq + 1) int32 token ids of job ``job``."""
    return rng(seed, STREAM_JOBS, job).integers(
        0, vocab, (traffic["rows_per_job"], traffic["seq"] + 1), dtype=np.int32)


def _lognormal(g, spec: dict, n: int) -> np.ndarray:
    x = g.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def requests(seed: int, traffic: dict, vocab: int, n: int) -> list:
    """The first ``n`` requests of the schedule: dicts with ``due_s``
    (seconds after the window opens), ``prompt`` (token ids),
    ``max_new`` and ``adapter`` (index)."""
    g = rng(seed, STREAM_REQUESTS)
    due = np.cumsum(g.exponential(1.0 / traffic["rate_per_s"], n))
    plen = _lognormal(g, traffic["prompt_len"], n)
    olen = _lognormal(g, traffic["output_len"], n)
    ranks = np.arange(1, traffic["n_adapters"] + 1, dtype=np.float64)
    pop = ranks ** -traffic["zipf_s"]
    adapters = g.choice(traffic["n_adapters"], n, p=pop / pop.sum())
    return [{"due_s": float(due[i]),
             "prompt": g.integers(0, vocab, int(plen[i]), dtype=np.int32),
             "max_new": int(olen[i]), "adapter": int(adapters[i])}
            for i in range(n)]
