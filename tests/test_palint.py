"""palint self-tests: every rule fires on a bad fixture and stays quiet
on the matching good one, suppressions work, ``--json`` has the
documented shape, and — the real gate — the repo itself lints clean.

Fixtures are miniature source trees written under ``tmp_path`` and
analyzed through the :func:`tools.palint.run` API with ``root`` pointed
at the fixture, so rule paths (``src/repro/models/...``) behave exactly
as in the real repo. palint never imports the code it analyzes, so the
fixtures only need to *parse*.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.palint import Context, run  # noqa: E402


def lint_tree(tmp_path, files, **ctx_kw):
    """Write ``{relpath: source}`` under tmp_path and lint the tree."""
    for rel, source in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(source)
    root = str(tmp_path)
    return run(root=root, ctx=Context(root=root, **ctx_kw))


def rules_fired(result):
    return {f.rule for f in result.findings}


# ---------------------------------------------------------------- compat


def test_compat_surface_flags_gated_apis_outside_compat(tmp_path):
    result = lint_tree(tmp_path, {
        "src/repro/models/m.py": (
            "from jax.experimental.shard_map import shard_map\n"
            "import jax\n"
            "mesh = jax.make_mesh((2,), ('dp',), axis_types=(1,))\n"
        ),
    })
    assert rules_fired(result) == {"compat-surface"}
    assert len(result.findings) == 2  # the import and the kwarg


def test_compat_surface_allows_compat_py(tmp_path):
    result = lint_tree(tmp_path, {
        "src/repro/compat.py": (
            "from jax.experimental.shard_map import shard_map\n"
            "from jax.sharding import AxisType\n"
        ),
    })
    assert result.ok, [f.render() for f in result.findings]


# -------------------------------------------------------------- layering


def test_layering_models_must_not_import_kernels(tmp_path):
    result = lint_tree(tmp_path, {
        "src/repro/models/m.py": "from repro.kernels import quant_matmul\n",
    })
    assert rules_fired(result) == {"layering"}


def test_layering_examples_must_not_touch_trainer_privates(tmp_path):
    result = lint_tree(tmp_path, {
        "examples/e.py": (
            "from repro.launch.train import _build_state\n"
            "import repro.launch.train as train\n"
            "train._run_epoch()\n"
        ),
    })
    assert rules_fired(result) == {"layering"}
    assert len(result.findings) == 2


_LAUNCH_IMPORTS = (
    "from repro.launch.train import main\n"
    "import repro.launch.fleet\n"
    "from repro import launch\n"
    "from ..launch import train\n"
    "from repro.launcher import x\n"  # another package: never flagged
)


@pytest.mark.parametrize("rel, n_flagged", [
    ("src/repro/core/steps.py", 4),
    ("src/repro/launch/train.py", 0),
])
def test_layering_only_launch_imports_launch(tmp_path, rel, n_flagged):
    """launch/ holds the entry points only: the library below it may not
    import repro.launch, in any spelling; launch/ itself may."""
    result = lint_tree(tmp_path, {rel: _LAUNCH_IMPORTS})
    assert len(result.findings) == n_flagged, [f.render() for f in result.findings]
    assert rules_fired(result) <= {"layering"}


def test_layering_core_may_import_kernels(tmp_path):
    result = lint_tree(tmp_path, {
        "src/repro/core/opset.py": "from repro.kernels import quant_matmul\n",
        "examples/e.py": "from repro.runtime import EdgeSession\n",
    })
    assert result.ok, [f.render() for f in result.findings]


# ------------------------------------------------------------ jit-purity


def test_jit_purity_flags_host_effects_in_traced_bodies(tmp_path):
    result = lint_tree(tmp_path, {
        "src/repro/core/s.py": (
            "import time\n"
            "import jax\n"
            "import numpy as np\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    print('tracing')\n"
            "    t = time.perf_counter()\n"
            "    noise = np.random.normal()\n"
            "    return x + noise + t\n"
        ),
    })
    assert rules_fired(result) == {"jit-purity"}
    assert len(result.findings) == 3


def test_jit_purity_resolves_pallas_call_kernel_by_name(tmp_path):
    result = lint_tree(tmp_path, {
        "src/repro/kernels/k.py": (
            "import jax\n"
            "from jax.experimental import pallas as pl\n"
            "def _kernel(x_ref, o_ref):\n"
            "    print('inside kernel')\n"
            "    o_ref[...] = x_ref[...]\n"
            "def launch(x):\n"
            "    return pl.pallas_call(\n"
            "        _kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype)\n"
            "    )(x)\n"
        ),
    })
    assert rules_fired(result) == {"jit-purity"}


def test_jit_purity_ignores_effects_outside_traced_code(tmp_path):
    result = lint_tree(tmp_path, {
        "src/repro/core/s.py": (
            "import time\n"
            "import jax\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return x * 2\n"
            "def bench(x):\n"
            "    t0 = time.perf_counter()\n"
            "    y = step(x)\n"
            "    print(time.perf_counter() - t0)\n"
            "    return y\n"
        ),
    })
    assert result.ok, [f.render() for f in result.findings]


# ------------------------------------------------------- pallas-blockspec

_PALLAS_HEADER = (
    "import jax\n"
    "import jax.numpy as jnp\n"
    "from jax.experimental import pallas as pl\n"
    "def _k(x_ref, o_ref):\n"
    "    o_ref[...] = x_ref[...]\n"
)


def test_blockspec_index_map_arity_must_match_grid_rank(tmp_path):
    result = lint_tree(tmp_path, {
        "src/repro/kernels/k.py": _PALLAS_HEADER + (
            "def launch(x):\n"
            "    return pl.pallas_call(\n"
            "        _k,\n"
            "        grid=(2, 2),\n"
            "        in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],\n"
            "        out_specs=pl.BlockSpec((8, 8), lambda i, j: (i, j)),\n"
            "        out_shape=jax.ShapeDtypeStruct((16, 16), jnp.float32),\n"
            "    )(x)\n"
        ),
    })
    assert rules_fired(result) == {"pallas-blockspec"}
    (finding,) = result.findings
    assert "index_map takes 1" in finding.message


def test_blockspec_block_must_divide_output_dim(tmp_path):
    result = lint_tree(tmp_path, {
        "src/repro/kernels/k.py": _PALLAS_HEADER + (
            "def launch(x):\n"
            "    return pl.pallas_call(\n"
            "        _k,\n"
            "        grid=(13,),\n"
            "        out_specs=pl.BlockSpec((8, 100), lambda i: (i, 0)),\n"
            "        out_shape=jax.ShapeDtypeStruct((100, 100), jnp.float32),\n"
            "    )(x)\n"
        ),
    })
    assert rules_fired(result) == {"pallas-blockspec"}
    (finding,) = result.findings
    assert "does not divide" in finding.message


def test_blockspec_vmem_budget_and_per_site_report(tmp_path):
    huge = _PALLAS_HEADER + (
        "def launch(x):\n"
        "    bm = 4096\n"
        "    return pl.pallas_call(\n"
        "        _k,\n"
        "        grid=(2,),\n"
        "        in_specs=[pl.BlockSpec((bm, bm), lambda i: (i, 0))],\n"
        "        out_specs=pl.BlockSpec((bm, bm), lambda i: (i, 0)),\n"
        "        out_shape=jax.ShapeDtypeStruct((8192, 4096), jnp.float32),\n"
        "    )(x)\n"
    )
    result = lint_tree(tmp_path, {"src/repro/kernels/k.py": huge})
    assert rules_fired(result) == {"pallas-blockspec"}
    (finding,) = result.findings
    assert "VMEM" in finding.message
    # every site gets an informational report, violation or not
    (report,) = result.reports
    # 2 blocks x (4096*4096*4 bytes) x2 double-buffering = 256 MiB
    assert report.data["vmem_bytes"] == 2 * 4096 * 4096 * 4 * 2
    assert report.data["exact"] is True

    # the same site passes with a raised budget
    ok = lint_tree(tmp_path, {"src/repro/kernels/k.py": huge},
                   vmem_budget_bytes=512 * 2**20)
    assert ok.ok


def test_blockspec_unwraps_prefetch_scalar_grid_spec(tmp_path):
    """grid_spec=PrefetchScalarGridSpec(...) sites get the same checks
    as flat kwargs, with index_map arity = grid rank + num_scalar_prefetch."""
    src = _PALLAS_HEADER + (
        "from jax.experimental.pallas import tpu as pltpu\n"
        "def launch(x):\n"
        "    spec = pl.BlockSpec((8, 8), lambda i, j, bt: (bt[i], j))\n"
        "    gs = pltpu.PrefetchScalarGridSpec(\n"
        "        num_scalar_prefetch=1,\n"
        "        grid=(2, 2),\n"
        "        in_specs=[spec],\n"
        "        out_specs=pl.BlockSpec((8, 8), lambda i, j: (i, j)),\n"
        "        scratch_shapes=[pltpu.VMEM((8, 8), jnp.float32)],\n"
        "    )\n"
        "    return pl.pallas_call(\n"
        "        _k,\n"
        "        grid_spec=gs,\n"
        "        out_shape=jax.ShapeDtypeStruct((16, 16), jnp.float32),\n"
        "    )(x)\n"
    )
    result = lint_tree(tmp_path, {"src/repro/kernels/k.py": src})
    # the out_spec lambda takes 2 args but the site expects 2 + 1 prefetch
    assert rules_fired(result) == {"pallas-blockspec"}
    (finding,) = result.findings
    assert "scalar-prefetch" in finding.message and "takes 2" in finding.message
    (report,) = result.reports
    assert report.data["num_scalar_prefetch"] == 1
    assert report.data["grid_rank"] == 2
    assert report.data["n_scratch"] == 1
    # 2 blocks ×(8·8·4)×2 double-buffer + 8·8·4 scratch
    assert report.data["vmem_bytes"] == 2 * 8 * 8 * 4 * 2 + 8 * 8 * 4


def test_blockspec_assert_envelope_bounds_vmem_estimate(tmp_path):
    """`assert dim <= N` declares a ceiling for a runtime-unpacked dim —
    the VMEM estimate uses it (inexactly) instead of --assume-dim."""
    src = _PALLAS_HEADER + (
        "def launch(x):\n"
        "    bm, bn = x.shape\n"
        "    assert bm <= 8 and bn <= 16\n"
        "    return pl.pallas_call(\n"
        "        _k,\n"
        "        grid=(2,),\n"
        "        in_specs=[pl.BlockSpec((bm, bn), lambda i: (i, 0))],\n"
        "        out_specs=pl.BlockSpec((bm, bn), lambda i: (i, 0)),\n"
        "        out_shape=jax.ShapeDtypeStruct((16, 16), jnp.float32),\n"
        "    )(x)\n"
    )
    result = lint_tree(tmp_path, {"src/repro/kernels/k.py": src})
    assert result.ok, [f.render() for f in result.findings]
    (report,) = result.reports
    assert report.data["vmem_bytes"] == 2 * 8 * 16 * 4 * 2
    assert report.data["exact"] is False
    assert report.data["assumed_dims"] == []


def test_blockspec_clean_site_reports_but_does_not_fire(tmp_path):
    result = lint_tree(tmp_path, {
        "src/repro/kernels/k.py": _PALLAS_HEADER + (
            "def launch(x):\n"
            "    return pl.pallas_call(\n"
            "        _k,\n"
            "        grid=(2, 2),\n"
            "        in_specs=[pl.BlockSpec((8, 8), lambda i, j: (i, j))],\n"
            "        out_specs=pl.BlockSpec((8, 8), lambda i, j: (i, j)),\n"
            "        out_shape=jax.ShapeDtypeStruct((16, 16), jnp.float32),\n"
            "    )(x)\n"
        ),
    })
    assert result.ok, [f.render() for f in result.findings]
    assert len(result.reports) == 1


# ------------------------------------------------------------- axis-name


def test_axis_name_flags_unbound_collective_axis(tmp_path):
    result = lint_tree(tmp_path, {
        "src/repro/core/c.py": (
            "import jax\n"
            "def allreduce(x):\n"
            "    return jax.lax.psum(x, 'dp')\n"
        ),
    })
    assert rules_fired(result) == {"axis-name"}


def test_axis_name_accepts_mesh_bound_axis(tmp_path):
    result = lint_tree(tmp_path, {
        "src/repro/core/c.py": (
            "import jax\n"
            "mesh = jax.make_mesh((2,), ('dp',))\n"
            "def allreduce(x):\n"
            "    return jax.lax.psum(x, 'dp')\n"
        ),
    })
    assert result.ok, [f.render() for f in result.findings]


# ---------------------------------------------------------- storage-form


def test_storage_form_flags_eager_dequant_outside_kernels(tmp_path):
    result = lint_tree(tmp_path, {
        "src/repro/models/m.py": (
            "import jax.numpy as jnp\n"
            "def widen(w):\n"
            "    return w['q'].astype(jnp.float32) * w['scale']\n"
        ),
    })
    assert rules_fired(result) == {"storage-form"}


def test_storage_form_allows_kernels_and_cache(tmp_path):
    result = lint_tree(tmp_path, {
        "src/repro/kernels/k.py": (
            "import jax.numpy as jnp\n"
            "def widen(w):\n"
            "    return w['q'].astype(jnp.float32) * w['scale']\n"
        ),
    })
    assert result.ok, [f.render() for f in result.findings]


def test_storage_form_sanctions_paging_but_not_the_engine(tmp_path):
    """The paged-KV pool owns quantise-on-write; the serving engine and
    decode step must never widen an INT8 page outside the kernels."""
    widen = (
        "import jax.numpy as jnp\n"
        "def widen(page):\n"
        "    return page['q'].astype(jnp.float32) * page['scale']\n"
    )
    ok = lint_tree(tmp_path, {"src/repro/serve/paging.py": widen})
    assert ok.ok, [f.render() for f in ok.findings]
    bad = lint_tree(tmp_path, {"src/repro/serve/engine.py": widen})
    assert rules_fired(bad) == {"storage-form"}


# ----------------------------------------------------------- suppression


def test_per_line_suppression_silences_named_rule(tmp_path):
    result = lint_tree(tmp_path, {
        "src/repro/models/m.py": (
            "from repro.kernels import quant_matmul"
            "  # palint: disable=layering  -- fixture exercising suppression\n"
        ),
    })
    assert result.ok, [f.render() for f in result.findings]


def test_suppression_is_rule_specific(tmp_path):
    result = lint_tree(tmp_path, {
        "src/repro/models/m.py": (
            "from repro.kernels import quant_matmul"
            "  # palint: disable=compat-surface\n"
        ),
    })
    assert rules_fired(result) == {"layering"}


# --------------------------------------------------- CLI + self-run gate


def run_cli(*argv, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "tools.palint", *argv],
        capture_output=True, text=True, cwd=cwd,
    )


def test_cli_json_shape_and_repo_is_clean():
    proc = run_cli("--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert set(payload) == {"version", "ok", "files_scanned", "findings",
                            "reports"}
    assert payload["ok"] is True and payload["findings"] == []
    assert payload["files_scanned"] > 50
    for report in payload["reports"]:
        assert set(report) == {"rule", "path", "line", "data"}


def test_self_run_reports_vmem_for_every_pallas_site():
    result = run(root=REPO)
    assert result.ok, [f.render() for f in result.findings]
    sites = [r for r in result.reports if r.rule == "pallas-blockspec"]
    assert len(sites) >= 7  # the repo's pallas_call sites, all budgeted
    assert {r.path for r in sites} >= {
        "src/repro/kernels/quant_matmul.py",
        "src/repro/kernels/adapter_fuse.py",
        "src/repro/kernels/flash_attention.py",
        "src/repro/kernels/cached_step.py",
    }
    for r in sites:
        assert isinstance(r.data["vmem_bytes"], int)
        assert r.data["vmem_bytes"] <= r.data["budget_bytes"]


def test_cli_nonzero_exit_on_findings(tmp_path):
    (tmp_path / "src").mkdir()
    (tmp_path / "src" / "bad.py").write_text(
        "from jax.experimental.shard_map import shard_map\n"
    )
    proc = run_cli("--root", str(tmp_path), cwd=REPO)
    assert proc.returncode == 1
    assert "[compat-surface]" in proc.stdout
