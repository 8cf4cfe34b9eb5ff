"""Shared by the tests of ``correct``: the whole run of a cell at a
test's size in interpret mode, with the harness's look for a chip
skipped."""

import jax
import benchcells
from bench import manifest, run

SEED = 2 ** 33 + 5
M = manifest.manifest()
CELLS = [w["name"] for w in M["workloads"]]
# the faults a training cell can have (none spans chips)
FAULTS = ["half_batch", "unchanged"]


def run_small(name):
    return run.run_cell(benchcells.small_cell(name), SEED, 0.5, False, jax.devices()[:1],
                        run.T_START)


