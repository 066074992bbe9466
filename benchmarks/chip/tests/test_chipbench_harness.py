"""The chip benchmark's command and harness, driven on the CPU at smoke
size: the timed path through the colony, the check that decides
``correct``, and that check failing when the timed path is broken."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]

RUNNER = r"""
import json, sys, time
import numpy as np
sys.path[:0] = [sys.argv[1], sys.argv[2]]
from chipbench.cell import load
from chipbench.harness import run_cell

def token(engine):
    inner = engine.generate
    def generate(tokens, max_new_tokens=16, *a, **k):
        out = np.array(inner(tokens, max_new_tokens, *a, **k))
        out[:, -1] = (out[:, -1] + 1) % engine.cfg.vocab_size  # altered where produced
        return out
    engine.generate = generate

def stale_cache(engine):
    inner = engine._decode
    engine._decode = lambda p, t, c, pos: (inner(p, t, c, pos)[0], c)  # state unchanged

def control(engine):
    # the fp8 control in the program's place: at each served position, the
    # token that the reference computed from fp8 operands puts first
    import jax.numpy as jnp
    from chipbench import reference, system, traffic, weights
    dims = system.dims_of(engine.cfg)
    w = weights.make(dims, traffic.seed32(SEED, traffic.STREAM_WEIGHTS),
                     jnp.dtype(engine.cfg.param_dtype))
    inner = engine.generate
    def generate(tokens, max_new_tokens=16, *a, **k):
        out = np.asarray(inner(tokens, max_new_tokens, *a, **k))
        seq = jnp.asarray(np.concatenate([tokens, out[:, :-1]], 1))
        ctl = reference.logits(w, dims, seq, tokens.shape[1] - 1, quant=True)
        return np.asarray(ctl).argmax(-1).astype(out.dtype)
    engine.generate = generate

def raises(engine):
    def generate(*a, **k):
        raise RuntimeError("injected engine failure")
    engine.generate = generate

SEED = 2**31 + 77
cell = load(sys.argv[3])
plen = 24
cell.traffic = dict(cell.traffic, prompt_tokens=plen, rate_per_s=3.0, max_len=plen + 8,
                    drain_s=30, generator={"queuesize": 2, "timeout_s": 0.5},
                    output_tokens=dict(cell.traffic["output_tokens"], min=4, max=8, median=6),
                    check={"tokens": 40, "rows_per_call": 4})
fault = {"none": None, "token": token, "stale_cache": stale_cache, "control": control,
         "raises": raises}[sys.argv[4]]
line, notes = run_cell(cell, SEED, 3.0, bool(int(sys.argv[5])), t_start=time.time(),
                       variant="smoke", require_chip=False, fault=fault)
print("\n".join(notes), file=sys.stderr)
print(json.dumps(line))
"""


def _env(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("PYTHONPATH", None)
    return env


def drive(tmp_path, workload, fault="none", traced=False):
    proc = subprocess.run(
        [sys.executable, "-c", RUNNER, str(BENCH), str(ROOT / "src"), workload, fault,
         str(int(traced))], capture_output=True, text=True, timeout=600, env=_env(tmp_path),
        cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


@pytest.mark.parametrize("workload,traced", [("stablelm-3b.chat", False),
                                             ("granite-3-8b-stage.rag", True)])
def test_smoke_run_is_correct(tmp_path, workload, traced):
    line, err = drive(tmp_path, workload, traced=traced)
    assert line["correct"] is True, err[-3000:]
    assert line["attempted"] == 9 and line["failed"] == 0
    assert list(line)[-1] == "checked"
    assert line["checked"]["max_gap"]["value"] <= line["checked"]["max_gap"]["limit"]
    assert "check max_gap" in err.strip().splitlines()[-4]
    if traced:
        # generate's eager concatenate builds a program per batch size and
        # output length it meets; nothing warms those, so the window counts them
        assert line["metrics"]["compiles_in_window"]["value"] >= 1
        assert line["metrics"]["batch_wait_s"]["value"] > 0
        assert line["device"]["window_s"] > 0
    else:
        assert set(line["metrics"]) == {"latency_p50_s", "output_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["token", "stale_cache", "control"])
def test_broken_timed_path_is_not_correct(tmp_path, fault):
    line, err = drive(tmp_path, "stablelm-3b.chat", fault)
    assert line["correct"] is False, err[-3000:]
    assert line["checked"]["max_gap"]["value"] > line["checked"]["max_gap"]["limit"]


def test_failed_batches_count_as_failed_requests(tmp_path):
    line, err = drive(tmp_path, "stablelm-3b.chat", "raises")
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] == 9
    assert line["checked"]["failed"]["value"] == 9


def test_command_refuses_without_a_tpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "stablelm-3b.chat", "--seed", "1",
         "--seconds", "1", "--trace", "0"], capture_output=True, text=True, timeout=300,
        env=_env(tmp_path), cwd=str(ROOT))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_command_needs_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files has
    no system to measure: no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "stablelm-3b.chat", "--seed", "1",
         "--seconds", "1", "--trace", "0"], capture_output=True, text=True, timeout=300,
        env=dict(_env(tmp_path), JAX_PLATFORMS="cpu"), cwd=str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_load_generator_imports_no_jax():
    code = ("import sys; sys.path[:0] = [sys.argv[1], sys.argv[2]]\n"
            "import chipbench.loadgen, chipbench.traffic\n"
            "import repro.core.http_transport, repro.core.fs, repro.serve.batcher\n"
            "assert 'jax' not in sys.modules, [m for m in sys.modules if 'jax' in m]\n")
    subprocess.run([sys.executable, "-c", code, str(BENCH), str(ROOT / "src")], check=True,
                   timeout=120)
