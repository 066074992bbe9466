"""End-to-end and per-layer readings of the chip benchmark, on made-up runs."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import blocks, work  # noqa: E402
from chipbench.cell import reader  # noqa: E402
from chipbench.harness import Run, output_tokens_per_s, percentile  # noqa: E402
from chipbench.peaks import peaks_for  # noqa: E402

dense = blocks.load("dense")
D = dense.Dims(layers=2, d_model=64, heads=4, kv_heads=2, head_dim=16, d_ff=208, vocab=259,
               tied=True, norm="rmsnorm", norm_eps=1e-5, rope_theta=1e4)
T0 = 1000.0


def rec(i, due, latency=None, max_new=16, served=32, failed=False, rid=None):
    answered = latency is not None
    return {"i": i, "due": due, "max_new": max_new, "rid": rid or f"r{i}", "lag": 0.001,
            "sent_wall": T0 + due + 0.001, "latency": latency,
            "recv_wall": T0 + due + latency if answered else None,
            "tokens": [1] * served if answered else None, "failed": failed}


def test_percentile_ranks_misses_last():
    recs = [rec(i, 0.0, latency=1.0 + i) for i in range(8)] + [rec(8, 0.0), rec(9, 0.0, failed=True)]
    assert percentile(recs, T0 + 60.0, T0, 50) == 5.0
    # ranks 9 and 10 are the two misses: worse than every answer, valued
    # at the wait they had when the run ended
    assert percentile(recs, T0 + 60.0, T0, 90) == 60.0
    assert percentile(recs[:8], T0 + 60.0, T0, 90) == 8.0


def test_output_rate_counts_tokens_asked_for_not_padded():
    recs = [rec(0, 0.0, latency=2.0, max_new=16, served=128),
            rec(1, 1.0, latency=3.0, max_new=48, served=128), rec(2, 2.0)]
    assert output_tokens_per_s(recs, T0) == pytest.approx((16 + 48) / 4.0)


def _run(trace=None):
    recs = [rec(0, 0.0, latency=3.0, rid="a"), rec(1, 0.5, latency=3.2, rid="b")]
    proc = {"state": "successful", "submissiontime": int((T0 + 2.0) * 1e9),
            "starttime": int((T0 + 2.1) * 1e9), "endtime": int((T0 + 2.9) * 1e9),
            "spec": {"kwargs": {"packed_args": [{"request_id": "a"}, {"request_id": "b"}]}}}
    calls = [{"start": T0 + 2.2, "end": T0 + 2.7, "batch": 2, "seq": 8, "new": 4}]
    return Run(block=dense, dims=D, peaks=peaks_for("TPU v5 lite"), seconds=10.0, t0_wall=T0,
               end_wall=T0 + 12, records=recs, processes=[proc], calls=calls,
               compiles_in_window=0, memory_peak_bytes=12_345_000_000, trace=trace)


def test_host_readers():
    run = _run()
    assert reader("batch_wait_s")(run) == pytest.approx(((2.0 - 0.001) + (1.5 - 0.001)) / 2)
    assert reader("assign_wait_s")(run) == pytest.approx(0.1)
    assert reader("executor_overhead_s")(run) == pytest.approx(0.8 - 0.5)
    assert reader("result_pickup_s")(run) == pytest.approx(((3.0 - 2.7) + (3.7 - 2.7)) / 2)
    assert reader("engine_ms_per_step")(run) == pytest.approx(500.0 / 4)
    assert reader("compiles_in_window")(run) == 0.0
    assert reader("peak_hbm_gb")(run) == pytest.approx(12.345)


def test_trace_readers_are_silent_without_a_trace():
    run = _run()
    for name in ("prefill_device_ms", "decode_step_device_ms", "step_mfu", "step_mfu.latency",
                 "decode_step_roofline", "prefill_roofline", "device_idle_share"):
        assert reader(name)(run) is None


def test_trace_readers():
    calls = {"jit_prefill_fn": [0.002], "jit_serve_step": [0.001, 0.001, 0.003]}
    run = _run({"busy_s": 0.5, "window_s": 2.0, "calls": calls})
    assert reader("device_idle_share")(run) == pytest.approx(0.75)
    assert reader("prefill_device_ms")(run) == pytest.approx(2.0)
    assert reader("decode_step_device_ms")(run) == pytest.approx(5 / 3)
    peaks = run.peaks
    decodes = [work.roofline_s(dense, D, "decode", 2, p, peaks) for p in (8, 9, 10)]
    assert reader("decode_step_roofline")(run) == pytest.approx(100 * (sum(decodes) / 3) / (0.005 / 3))
    assert reader("prefill_roofline")(run) == pytest.approx(
        100 * work.roofline_s(dense, D, "prefill", 2, 8, peaks) / 0.002)
    ops = dense.prefill_flops(D, 2, 8) + sum(dense.decode_flops(D, 2, p) for p in (8, 9, 10))
    assert reader("step_mfu")(run) == pytest.approx(100 * ops / (2.0 * 197e12))
    assert reader("step_mfu.latency")(run) == reader("step_mfu")(run)
