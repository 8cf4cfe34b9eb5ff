"""The traffic generator: deterministic in the seed, different across seeds."""

import numpy as np
import pytest

import benchcells  # noqa: F401  (puts the checkout's root on sys.path)
from bench import manifest, traffic

SEEDS = [0, 7, 2 ** 31 + 11, 2 ** 33 + 5]


@pytest.mark.parametrize("mix", ["capture", "cached"])
def test_jobs_repeat_for_a_seed_and_differ_across_seeds_and_jobs(mix):
    tr = manifest.load_json(f"{manifest.BENCH}/traffic/{mix}.json")
    tr = dict(tr, rows_per_job=8, seq=64)
    first = [traffic.job_tokens(s, 0, tr, 92544) for s in SEEDS]
    for s, toks in zip(SEEDS, first):
        assert toks.shape == (8, 65) and toks.dtype == np.int32
        assert toks.min() >= 0 and toks.max() < 92544
        np.testing.assert_array_equal(toks, traffic.job_tokens(s, 0, tr, 92544))
        assert not np.array_equal(toks, traffic.job_tokens(s, 1, tr, 92544))
        assert len({r.tobytes() for r in toks}) == len(toks)  # rows all differ
    assert len({t.tobytes() for t in first}) == len(SEEDS)


def test_request_schedule_is_seeded_and_within_its_limits():
    tr = {"rate_per_s": 4.0, "n_adapters": 8, "zipf_s": 1.1,
          "prompt_len": {"median": 256, "sigma": 0.8, "min": 16, "max": 1024},
          "output_len": {"median": 64, "sigma": 0.7, "min": 8, "max": 256}}
    a = traffic.requests(2 ** 33 + 5, tr, 1000, 200)
    b = traffic.requests(2 ** 33 + 5, tr, 1000, 200)
    c = traffic.requests(2 ** 33 + 6, tr, 1000, 200)
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert [r["due_s"] for r in a] != [r["due_s"] for r in c]
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    due = [r["due_s"] for r in a]
    assert due == sorted(due)
    assert all(16 <= len(r["prompt"]) <= 1024 and 8 <= r["max_new"] <= 256 for r in a)
    counts = np.bincount([r["adapter"] for r in a], minlength=8)
    assert counts[0] == counts.max()  # Zipf: the first adapter is the most popular
