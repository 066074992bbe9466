"""The program's spans set against the chip's idle time: the per-layer
readers on made-up runs, the attribution on the profiler's clock, and
the recorded extracts from a TPU v5e."""

import json
import os
import subprocess
import sys
import threading
from bisect import bisect_right
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parents[1] / "src")]

from chipbench import blocks, program_spans, trace  # noqa: E402
from chipbench.cell import reader  # noqa: E402
from chipbench.harness import CompileCounter, Run  # noqa: E402

TESTDATA = BENCH / "testdata"
dense = blocks.load("dense")
D = dense.Dims(layers=2, d_model=64, heads=4, kv_heads=2, head_dim=16, d_ff=208, vocab=259,
               tied=True, norm="rmsnorm", norm_eps=1e-5, rope_theta=1e4)
IDLE = ("idle_no_batch_share", "idle_executor_share", "idle_engine_share")
NEW = IDLE + ("generator_hold_s", "signature_cpu_share", "compile_s_in_window")
MS = 1_000_000


def _run(end_wall, window_s, busy_s):
    return Run(block=dense, dims=D, peaks=None, seconds=window_s, t0_wall=end_wall - window_s,
               end_wall=end_wall, records=[], processes=[], calls=[], compiles_in_window=0,
               memory_peak_bytes=None, trace={"busy_s": busy_s, "window_s": window_s})


@pytest.fixture()
def recording():
    from repro.core import tracing

    tracing.drain()
    tracing.enable()
    yield tracing
    tracing.disable()
    tracing.drain()


def test_readers_split_the_idle_time_by_the_executors_spans(recording):
    """Window [100, 110] s, busy 2 s (all inside generate). The executor
    waits in assign for 3 s and generates for 4 s; another thread's RPC
    spans overlap both and change nothing."""
    rec = recording.record

    def http_thread():
        rec("colony.server.handle", 101.0, 106.0)
        rec("colony.rpc.verify", 101.0, 106.0, cpu_s=0.5)

    rec("colony.broker.assign_wait", 99.0, 103.0)  # 1 s of it before the window
    rec("colony.engine.generate", 104.0, 108.0)
    rec("colony.generator.fire", 103.5, 103.6, packs=3, mean_hold_s=1.0, oldest_hold_s=2.0)
    rec("colony.generator.fire", 107.0, 107.1, packs=1, mean_hold_s=2.0, oldest_hold_s=2.0)
    rec("colony.rpc.sign", 108.5, 108.6, cpu_s=0.25)
    rec("colony.jax.compile", 104.5, 105.0, fun="concatenate", phase="compile")
    t = threading.Thread(target=http_thread)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    run = _run(110.0, 10.0, 2.0)
    got = {m: reader(m)(run) for m in NEW}
    assert got["idle_no_batch_share"] == pytest.approx(0.3)
    assert got["idle_engine_share"] == pytest.approx((4.0 - 2.0) / 10)
    assert got["idle_executor_share"] == pytest.approx((10 - 4 - 3) / 10)
    assert sum(got[m] for m in IDLE) == pytest.approx(reader("device_idle_share")(run), abs=1e-12)
    assert got["generator_hold_s"] == pytest.approx((3 * 1.0 + 2.0) / 4)
    assert got["signature_cpu_share"] == pytest.approx(0.75 / 10)
    assert got["compile_s_in_window"] == pytest.approx(0.5)


def test_idle_shares_are_silent_when_the_chip_outruns_generate(recording):
    """Busy 5 s but generate open 4 s: device work ran outside the span,
    and the engine share would come out negative."""
    recording.record("colony.broker.assign_wait", 100.0, 103.0)
    recording.record("colony.engine.generate", 104.0, 108.0)
    run = _run(110.0, 10.0, 5.0)
    assert all(reader(m)(run) is None for m in IDLE)
    assert reader("device_idle_share")(run) == pytest.approx(0.5)


def test_readers_are_silent_on_a_program_without_spans(monkeypatch):
    run = _run(110.0, 10.0, 2.0)
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)  # import fails
    assert all(reader(m)(run) is None for m in NEW)
    untraced = _run(110.0, 10.0, 2.0)
    untraced.trace = None
    assert all(reader(m)(untraced) is None for m in NEW)


def test_the_programs_compile_counter_agrees_with_the_harness(recording):
    import jax
    import jax.numpy as jnp

    import repro.serve.engine  # noqa: F401 — registers the compile listener

    def compiled():
        return sum(v["n"] for k, v in recording.counters().items()
                   if k.startswith("jax.compiles."))

    before = compiled()
    with CompileCounter() as harness:
        for n in (3, 5, 7):
            jax.block_until_ready(jax.jit(lambda x: jnp.sin(x) * n)(jnp.ones(n)))
        jax.block_until_ready(jnp.concatenate([jnp.ones((2, 3)), jnp.ones((2, 11))], axis=1))
    assert harness.count >= 4
    assert compiled() - before == harness.count
    spans = recording.drain()["spans"]
    assert {s["attrs"]["phase"] for s in spans if s["name"] == "colony.jax.compile"} == {
        "trace", "lower", "compile"}


def _two_thread_extract():
    """Chip busy at [15, 30] and [40, 55] ms of a [0, 160] ms window.
    Thread 0 runs generate [10, 60] inside process [0, 100], then waits
    in assign [100, 150]; thread 1 serves an RPC [60, 100]."""
    colony = [["colony.executor.process", 0, 100 * MS, 0],
              ["colony.engine.generate", 10 * MS, 50 * MS, 0],
              ["colony.engine.collect", 52 * MS, 8 * MS, 0],
              ["colony.broker.assign_wait", 100 * MS, 50 * MS, 0],
              ["colony.server.handle", 60 * MS, 40 * MS, 1],
              ["colony.rpc.verify", 62 * MS, 36 * MS, 1]]
    programs = [["jit_prefill_fn", 15 * MS, 15 * MS], ["jit_serve_step", 40 * MS, 15 * MS]]
    return programs, colony


def test_another_threads_rpc_does_not_label_the_executors_gap():
    programs, colony = _two_thread_extract()
    got = program_spans.attribute(programs, colony, 0, 160 * MS)
    gaps = dict(got["idle_gaps"])
    assert "colony.rpc.verify" not in gaps and "colony.server.handle" not in gaps
    assert gaps["colony.executor.process"] == pytest.approx(0.010 + 0.040)
    assert gaps["colony.engine.generate"] == pytest.approx(0.005 + 0.010)
    assert gaps["colony.engine.collect"] == pytest.approx(0.005)
    assert gaps["colony.broker.assign_wait"] == pytest.approx(0.050)
    assert gaps[program_spans.NO_SPAN] == pytest.approx(0.010)
    shares = got["shares"]
    assert shares == pytest.approx({"no_batch": 50 / 160, "engine": 20 / 160,
                                    "executor": 60 / 160})
    red = trace.reduce({"programs": {"0": programs}, "ops": {},
                        "spans": [[trace.WINDOW_SPAN, 0, 160 * MS]]})
    assert sum(shares.values()) == pytest.approx(1 - red["busy_s"] / red["window_s"])
    assert got["labelled_share"] == pytest.approx(1 - 10 / 130)


def test_the_existing_recorded_extract_reduces_as_before():
    """The recorded window has no colony spans: every number of the
    reduction is what it was before the program had them."""
    red = trace.reduce(json.loads((TESTDATA / "trace_stablelm-3b.chat.json").read_text()))
    assert red["busy_s"] == pytest.approx(4.26200208, abs=1e-12)
    assert red["window_s"] == pytest.approx(5.061516821, abs=1e-12)
    assert {k: (len(v), sum(v)) for k, v in red["calls"].items()} == {
        "jit_prefill_fn": (2, pytest.approx(0.089905034)),
        "jit_convert_element_type": (224, pytest.approx(0.000133743)),
        "jit__threefry_seed": (2, pytest.approx(1.335e-06)),
        "jit_sample_token": (224, pytest.approx(0.000860319)),
        "jit__threefry_split": (222, pytest.approx(0.000733178)),
        "jit__unstack": (222, pytest.approx(0.000190485)),
        "jit_serve_step": (222, pytest.approx(4.170118172)),
        "jit_concatenate": (16, pytest.approx(5.9814e-05))}
    assert red["device_ops"] == []
    assert red["idle_gaps"] == [
        ["outside engine.generate: jit_concatenate -> jit_prefill_fn", pytest.approx(0.654407969)],
        ["outside engine.generate: jit_concatenate -> window close", pytest.approx(0.053463051)],
        ["outside engine.generate: window open -> jit_prefill_fn", pytest.approx(0.051388106)],
        ["bench.engine_generate: jit_concatenate -> jit_concatenate", pytest.approx(0.031647005)],
        ["bench.engine_generate: jit__unstack -> jit_convert_element_type",
         pytest.approx(0.003645942)],
        ["bench.engine_generate: jit_convert_element_type -> jit_serve_step",
         pytest.approx(0.001967301)],
        ["bench.engine_generate: jit__threefry_split -> jit__unstack",
         pytest.approx(0.001249358)],
        ["bench.engine_generate: jit_sample_token -> jit__threefry_split",
         pytest.approx(0.001214828)],
        ["bench.engine_generate: jit_serve_step -> jit_sample_token", pytest.approx(0.000517306)],
        ["bench.engine_generate: jit__threefry_seed -> jit_sample_token", pytest.approx(4.58e-06)]]


def _split_by_brute_force(programs, colony, lo, hi):
    """The attribution done another way: cut [lo, hi] at every program's
    and every executor span's edge; a piece is idle if no program covers
    its middle, and belongs to the latest-begun executor span covering
    it. Returns (idle seconds by span name, the three shares)."""
    thread = program_spans.executor_thread([{"name": sp[0], "thread": sp[3]} for sp in colony])
    mine = [sp for sp in colony if sp[3] == thread]
    busy = sorted((s, s + d) for _, s, d in programs)
    edges = sorted({lo, hi} | {t for s, e in busy for t in (s, e) if lo < t < hi}
                   | {t for _, s, d, _ in mine for t in (s, s + d) if lo < t < hi})
    starts = [s for s, _ in busy]
    reach = []  # the furthest end among the programs begun by each start
    for _, e in busy:
        reach.append(max(e, reach[-1]) if reach else e)
    gaps, shares = {}, {"no_batch": 0.0, "executor": 0.0, "engine": 0.0}
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        k = bisect_right(starts, mid)
        if k and reach[k - 1] > mid:
            continue
        over = [sp for sp in mine if sp[1] <= mid < sp[1] + sp[2]]
        name = max(over, key=lambda sp: (sp[1], -sp[2]))[0] if over else program_spans.NO_SPAN
        names = {sp[0] for sp in over}
        gaps[name] = gaps.get(name, 0.0) + (b - a) / 1e9
        part = ("engine" if program_spans.GENERATE in names
                else "no_batch" if program_spans.ASSIGN_WAIT in names else "executor")
        shares[part] += (b - a) / 1e9
    return gaps, {k: v / ((hi - lo) / 1e9) for k, v in shares.items()}


def test_recorded_chip_trace_with_colony_spans():
    """Two batches of a traced chat window on a TPU v5e (program tracing
    on), an assign wait between them. The attribution on the profiler's
    clock against three witnesses: the readers' split of the same batches
    from the program's spans on the host's clock, the same split done by
    brute force, and idle gaps summed by hand over the executor's leaf
    spans; then against what it gave on the chip. Every idle gap is named
    by a span of the executor's own thread."""
    sample = json.loads((TESTDATA / "trace_stablelm-3b.chat.colony.json").read_text())
    want = sample["expected"]
    red = trace.reduce(sample)
    idle_share = 1 - red["busy_s"] / red["window_s"]
    assert idle_share == pytest.approx(want["device_idle_share"])
    w = next(sp for sp in sample["spans"] if sp[0] == trace.WINDOW_SPAN)
    lo, hi = w[1], w[1] + w[2]
    programs, colony = sample["programs"]["0"], sample["colony_spans"]
    got = program_spans.attribute(programs, colony, lo, hi)
    gaps = dict(got["idle_gaps"])

    lo_wall, hi_wall = sample["host_window"]
    run = _run(hi_wall, hi_wall - lo_wall, red["busy_s"])
    host = program_spans.idle_shares(run, [{"name": n, "start": s, "end": e, "thread": t}
                                           for n, s, e, t in sample["host_spans"]])
    assert min(got["shares"].values()) > 0.05  # each of the three is there
    assert host == pytest.approx(got["shares"], abs=1e-3)
    assert host == pytest.approx(want["host_shares"])

    brute_gaps, brute_shares = _split_by_brute_force(programs, colony, lo, hi)
    assert got["shares"] == pytest.approx(brute_shares, abs=1e-9)
    assert gaps == pytest.approx(brute_gaps, abs=1e-9)

    executor = program_spans.executor_thread([{"name": sp[0], "thread": sp[3]} for sp in colony])
    mine = [sp for sp in colony if sp[3] == executor]
    busy = [(s, s + d) for _, s, d in programs]

    def idle_in(s, e):  # the programs on one chip do not overlap
        return ((e - s) - sum(max(0, min(e, b1) - max(s, b0)) for b0, b1 in busy)) / 1e9

    for leaf in ("colony.rpc.sign", "colony.rpc.verify", "colony.engine.collect"):
        by_hand = sum(idle_in(max(s, lo), min(s + d, hi)) for n, s, d, _ in mine if n == leaf)
        assert by_hand > 0
        assert gaps[leaf] == pytest.approx(by_hand, abs=1e-9)

    assert got["shares"] == pytest.approx(want["attribution"]["shares"])
    assert got["idle_gaps"] == [[k, pytest.approx(v)] for k, v in want["attribution"]["idle_gaps"]]
    assert sum(got["shares"].values()) == pytest.approx(idle_share)
    assert got["labelled_share"] >= 0.9
    others = {sp[3] for sp in colony} - {executor}
    assert others  # the HTTP threads' RPC spans are in the extract...
    assert set(gaps) <= {sp[0] for sp in mine} | {program_spans.NO_SPAN}  # ...and name nothing


ATTRIBUTE_RUNNER = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[2]]
from chipbench.cell import load
import attribute

cell = load("stablelm-3b.chat")
plen = 24
cell.traffic = dict(cell.traffic, prompt_tokens=plen, rate_per_s=3.0, max_len=plen + 8,
                    drain_s=30, generator={"queuesize": 2, "timeout_s": 0.5},
                    output_tokens=dict(cell.traffic["output_tokens"], min=4, max=8, median=6),
                    check={"tokens": 40, "rows_per_call": 4})
line, notes, reading = attribute.attributed(cell, 2**31 + 77, 3.0, time.time(), sys.argv[3],
                                            variant="smoke", require_chip=False)
print(json.dumps(reading))
"""


def test_attribution_and_readers_agree_on_one_smoke_window(tmp_path):
    """``attribute.py``'s run at smoke size on the CPU: the readers' split
    on the host's clock and the attribution on the profiler's clock, of
    one window, and of the two batches its sample keeps. No program shows
    on a chip's line here, so the whole window is idle and the test holds
    the two clocks' split of the executor thread's time to each other."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "-c", ATTRIBUTE_RUNNER, str(BENCH), str(BENCH.parents[1] / "src"),
         str(tmp_path / "sample.json")], capture_output=True, text=True, timeout=600, env=env,
        cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-4000:]
    reading = json.loads(proc.stdout.strip().splitlines()[-1])
    assert reading["correct"] is True
    assert reading["colony_spans"] > 0 and reading["labelled_share"] >= 0.9
    assert reading["readers"] == pytest.approx(reading["attributed"], abs=1e-3)
    assert sum(reading["readers"].values()) == pytest.approx(reading["device_idle_share"])
    want = json.loads((tmp_path / "sample.json").read_text())["expected"]
    assert want["host_shares"] == pytest.approx(want["attribution"]["shares"], abs=1e-3)
