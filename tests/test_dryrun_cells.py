"""Dry-run machinery on a small host-device mesh (subprocess: needs its
own XLA_FLAGS before jax import)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax
    from repro.launch.dryrun_lib import run_cell
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    recs = []
    for arch, shape in {cells}:
        recs.append(run_cell(arch, shape, mesh, cfg_overrides={overrides}))
    print("RESULT::" + json.dumps(recs))
    """
)


def _run_cells(cells, overrides=None):
    script = _SCRIPT.format(cells=repr(cells), overrides=repr(overrides or {}))
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env["JAX_PLATFORMS"] = "cpu"  # the child wants host devices, not a chip
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, timeout=1200, cwd=ROOT,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    payload = [l for l in out.stdout.splitlines() if l.startswith("RESULT::")][0]
    return json.loads(payload[len("RESULT::"):])


@pytest.mark.slow
def test_train_prefill_decode_cells_compile():
    recs = _run_cells([
        ("seamless-m4t-large-v2", "train_4k"),
        ("stablelm-3b", "prefill_32k"),
        ("mixtral-8x7b", "decode_32k"),
    ])
    for rec in recs:
        assert rec["status"] == "ok", rec.get("error")
        r = rec["roofline"]
        assert r["flops_per_device"] > 0
        assert r["bytes_per_device"] > 0
        assert r["bottleneck"] in ("compute", "memory", "collective")
        # collective traffic must exist on a sharded mesh
        assert rec["collectives"]["total_bytes"] > 0


@pytest.mark.slow
def test_long_context_skip_policy():
    recs = _run_cells([
        ("qwen2.5-14b", "long_500k"),  # pure attention -> skipped
        ("rwkv6-7b", "long_500k"),  # SSM -> runs
    ])
    assert recs[0]["status"] == "skipped"
    assert "sub-quadratic" in recs[0]["reason"]
    assert recs[1]["status"] == "ok"


@pytest.mark.slow
def test_scan_loops_are_scaled():
    recs = _run_cells([("granite-3-8b", "train_4k")])
    rec = recs[0]
    trips = rec["loop_trip_counts"]
    assert any(v == 40 for v in trips.values()), trips  # 40 scanned layers
