"""The serving driver (``bench/serve.py``) on the chat mix at a test's
size: the open-loop client times every request and the comparison with
the reference separates the sound engine from its INT4 control and from
a token altered where the engine produces it. The chat mix is not a
cell of BENCHMARK.json until its rate is set by a sweep on the chip."""

import pytest

import benchcells
from bench import calibrate, serve
from bench.harness import now

SEED = 2 ** 33 + 5


def test_open_loop_run_times_every_request():
    out = serve.run(benchcells.small_serve_cell(), SEED, 0.5, t_start=now())
    assert out["attempted"] >= 5 and out["failed"] == 0
    assert out["compiles_in_window"] == 0
    assert len(out["ttft_ms"]) == out["attempted"] and all(t > 0 for t in out["ttft_ms"])
    assert out["e2e"]["itl_p95_ms"] > 0 and out["e2e"]["ttft_p95_ms"] > 0
    assert out["numbers"]["served_logit_gap"] < 1e-3


@pytest.mark.parametrize("variant", ["control", "altered"])
def test_control_and_altered_token_read_far_above_the_sound_engine(variant):
    got = calibrate.reading(benchcells.small_serve_cell(), SEED, variant, seconds=0.5)
    assert got["served_logit_gap"] > 0.5
