"""Readings on the chip that the benchmark's settings are made from.

    python benchmarks/chip/calibrate.py knee <workload> --rates 1,2,3 --seconds 20
    python benchmarks/chip/calibrate.py gaps <workload> --seeds 11,12,13 --seconds 15

``knee`` offers the cell's mix at each rate in turn, to one set-up, and
prints per rate the latency, the output rate, and whether the backlog
grew (the last quarter's median latency against the first quarter's,
and how long after the window closed the last answer came).

``gaps`` serves each seed's weights through the cell's mix at its rate,
then prints the widest gap of the program's served tokens and of the
tokens the fp8 control puts first at the same positions. These are the
readings the check's limit is set between. With ``--trace-sample``, the
first seed's window is traced and a few calls of it are written as a
recorded extract, for the test of the trace reduction.

Each reading is printed as one line of JSON.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def knee_reading(win: dict, rate: float, seconds: float) -> dict:
    from chipbench.harness import output_tokens_per_s, percentile

    recs = sorted(win["records"], key=lambda r: r["due"])
    q = max(1, len(recs) // 4)
    lat = lambda rs: statistics.median([r["latency"] for r in rs if r["latency"] is not None] or [0])
    answered = [r for r in recs if r["latency"] is not None]
    last = max((r["recv_wall"] for r in answered), default=win["t0"])
    return {
        "rate": rate, "requests": len(recs), "answered": len(answered),
        "p50": percentile(recs, win["end_wall"], win["t0"], 50),
        "p90": percentile(recs, win["end_wall"], win["t0"], 90),
        "first_quarter_median": lat(recs[:q]), "last_quarter_median": lat(recs[-q:]),
        "last_answer_after_close_s": last - win["t0"] - seconds,
        "output_tokens_per_s": output_tokens_per_s(recs, win["t0"]),
        "batches": len(win["calls"]),
        "mean_batch": statistics.mean([c["batch"] for c in win["calls"]] or [0]),
        "compiles": win["compiles"],
    }


def trace_sample(trace_dir: str, chips: int) -> dict:
    """Two generate calls of a traced window, with the reduction's numbers."""
    from chipbench import trace as tr

    ex = tr.extract(tr.find_xplane(trace_dir), chips)
    gens = sorted(sp for sp in ex["spans"] if sp[0] == "bench.engine_generate")[:2]
    lo, hi = gens[0][1] - 50e6, gens[-1][1] + gens[-1][2] + 50e6
    keep = lambda ev: lo <= ev[1] < hi
    sample = {
        "programs": {c: [e for e in evs if keep(e)] for c, evs in ex["programs"].items()},
        "ops": {}, "spans": [e for e in ex["spans"] if keep(e)] + [[tr.WINDOW_SPAN, lo, hi - lo]],
    }
    red = tr.reduce(sample)
    sample["expected"] = {
        "device_idle_share": 1 - red["busy_s"] / red["window_s"],
        "prefill_device_ms": 1000 * statistics.mean(red["calls"]["jit_prefill_fn"]),
        "decode_step_device_ms": 1000 * statistics.mean(red["calls"]["jit_serve_step"]),
    }
    return sample


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("knee", "gaps"))
    ap.add_argument("workload")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--drain", type=float, default=None)
    ap.add_argument("--trace-sample", default="")
    args = ap.parse_args()

    import jax

    from chipbench.cell import load
    from chipbench.harness import Session, verify
    from repro.launch.compile_cache import place_compile_cache

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate.py measures on a TPU only")
    place_compile_cache()
    cell = load(args.workload)
    traffic = dict(cell.traffic)
    if args.drain is not None:
        traffic["drain_s"] = args.drain
    seeds = [int(s) for s in args.seeds.split(",") if s] or [args.seed]
    with tempfile.TemporaryDirectory(prefix="chipbench-") as tmp:
        t = time.time()
        session = Session(cell, seeds[0], "full", tmp)
        emit({"workload": cell.name, "setup_s": time.time() - t})
        try:
            if args.what == "knee":
                for i, rate in enumerate(float(r) for r in args.rates.split(",")):
                    win = session.window(dict(traffic, rate_per_s=rate), args.seed + i,
                                         args.seconds)
                    emit({"workload": cell.name, **knee_reading(win, rate, args.seconds)})
                return
            for i, seed in enumerate(seeds):
                if i:
                    session.set_weights(seed)
                trace_dir = os.path.join(tmp, f"trace{i}") if args.trace_sample and i == 0 \
                    else None
                win = session.window(traffic, seed, args.seconds, trace_dir)
                peak = jax.devices()[0].memory_stats().get("peak_bytes_in_use")
                session.free_weights()
                t = time.time()
                v = verify(session, win["records"], seed, args.seconds, control=True)
                emit({"workload": cell.name, "seed": seed, **v,
                              "answered": sum(r["tokens"] is not None for r in win["records"]),
                              "attempted": len(win["records"]), "compiles": win["compiles"],
                              "reference_s": time.time() - t, "peak_bytes_in_use": peak})
                if trace_dir is not None:
                    Path(args.trace_sample).write_text(
                        json.dumps(trace_sample(trace_dir, cell.chips)))
        finally:
            session.close()


if __name__ == "__main__":
    main()
