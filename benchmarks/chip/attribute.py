"""Where the chip's idle time goes, by the program's own spans.

    python benchmarks/chip/attribute.py --workload stablelm-3b.chat --seed 7 --seconds 50 \\
        [--program-tracing off] [--sample attribution/sample.json]

Runs the cell once, traced, as ``run.py --trace 1`` does (the harness's
``run_cell``, the check included), and prints its result line. Then it
prints one more line of JSON from the same window: chip 0's idle time on
the profiler's clock split by the innermost colony span open on the
executor's thread (``program_spans.attribute``), beside the per-layer
readers' shares. The line also carries ``executor_overhead_s``, which the
benchmark reads in chat and rag only. ``--program-tracing off`` takes the
program's annotations and spans out of the run, for what tracing costs.
``--sample`` writes two batches of the window, with the program's spans
on both clocks and what each split gave, as a recorded extract for the
tests.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

EXTRA = [{"name": "executor_overhead_s", "unit": "s"}]


def sample(ex: dict, colony: list[list], host: list[dict]) -> dict:
    """Two consecutive ``engine.generate`` calls, an assign wait between
    them where one is found, on the profiler's clock and on the host's."""
    from chipbench import program_spans, trace
    from chipbench.harness import Run

    thread = program_spans.executor_thread([{"name": sp[0], "thread": sp[3]} for sp in colony])
    mine = sorted(sp for sp in colony if sp[3] == thread)
    gens = [sp for sp in mine if sp[0] == program_spans.GENERATE][1:]  # the first may compile
    waited = [i for i in range(len(gens) - 1) if any(
        sp[0] == program_spans.ASSIGN_WAIT and gens[i][1] < sp[1] < gens[i + 1][1]
        and sp[2] > 1e8 for sp in mine)]
    a, b = (gens[waited[0]], gens[waited[0] + 1]) if waited else (gens[0], gens[1])
    lo, hi = a[1] - 50e6, b[1] + b[2] + 50e6
    inside = lambda ev: ev[1] < hi and ev[1] + ev[2] > lo
    out = {
        "programs": {"0": [e for e in ex["programs"].get("0", []) if lo <= e[1] < hi]},
        "ops": {},
        "spans": [e for e in ex["spans"] if inside(e) and e[0] != trace.WINDOW_SPAN]
        + [[trace.WINDOW_SPAN, lo, hi - lo]],
        "colony_spans": [e for e in colony if inside(e)],
    }
    # The same generate call on the host's clock: the one of the closest length.
    twin = min((s for s in host if s["name"] == program_spans.GENERATE),
               key=lambda s: abs(s["end"] - s["start"] - a[2] / 1e9))
    offset = twin["start"] - a[1] / 1e9
    lo_wall, hi_wall = lo / 1e9 + offset, hi / 1e9 + offset
    out["host_spans"] = [[s["name"], s["start"], s["end"], s["thread"]] for s in host
                         if s["start"] < hi_wall and s["end"] > lo_wall]
    out["host_window"] = [lo_wall, hi_wall]
    red = trace.reduce(out)
    run = Run(block=None, dims=None, peaks=None, seconds=0.0, t0_wall=lo_wall,
              end_wall=hi_wall, records=[], processes=[], calls=[], compiles_in_window=0,
              memory_peak_bytes=None, trace=red)
    out["expected"] = {
        "device_idle_share": 1 - red["busy_s"] / red["window_s"],
        "attribution": program_spans.attribute(out["programs"]["0"], out["colony_spans"], lo, hi),
        "host_shares": program_spans.idle_shares(run, [
            {"name": n, "start": s, "end": e, "thread": t} for n, s, e, t in out["host_spans"]]),
    }
    return out


def attributed(cell, seed: int, seconds: float, t_start: float, sample_path: str = "",
               **run_kw) -> tuple[dict, list[str], dict]:
    """One traced ``run_cell`` of ``cell``; its result line, its notes,
    and the split of its idle time by the two clocks."""
    from chipbench import program_spans, trace
    from chipbench.harness import run_cell

    seen: dict = {}
    extract = trace.extract

    def keep(path: str, chips: int) -> dict:  # the harness reduces, and deletes, the trace
        seen["ex"] = extract(path, chips)
        seen["colony"] = program_spans.extract(path)
        return seen["ex"]

    trace.extract = keep
    try:
        line, notes = run_cell(dataclasses.replace(cell, per_layer=cell.per_layer + EXTRA),
                               seed, seconds, True, t_start=t_start, **run_kw)
    finally:
        trace.extract = extract
    ex, colony = seen["ex"], seen["colony"]
    w = next(sp for sp in ex["spans"] if sp[0] == trace.WINDOW_SPAN)
    attr = program_spans.attribute(ex["programs"].get("0", []), colony, w[1], w[1] + w[2]) or {}
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    reading = {
        "workload": cell.name, "seed": seed, "correct": line["correct"],
        "colony_spans": len(colony),
        "readers": {k: metrics.get(f"idle_{k}_share") for k in ("no_batch", "executor", "engine")},
        "attributed": attr.get("shares"), "device_idle_share": metrics.get("device_idle_share"),
        "attributed_idle_share": attr.get("idle_share"),
        "labelled_share": attr.get("labelled_share"), "idle_gaps": attr.get("idle_gaps"),
        "metrics": metrics}
    if sample_path and colony:
        Path(sample_path).parent.mkdir(parents=True, exist_ok=True)
        Path(sample_path).write_text(json.dumps(sample(ex, colony, program_spans.last[1] or [])))
    return line, notes, reading


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--program-tracing", choices=("on", "off"), default="on")
    ap.add_argument("--sample", default="")
    args = ap.parse_args()

    from chipbench.cell import load
    from chipbench.harness import NoChip

    if args.program_tracing == "off":
        from repro.core import tracing

        import repro.serve.engine  # noqa: F401 — it installs the profiler hook at import

        tracing.install(None)
    try:
        line, notes, reading = attributed(load(args.workload), args.seed, args.seconds,
                                          T_START, args.sample)
    except NoChip as e:
        print(f"attribute.py: {e}", file=sys.stderr)
        return 3
    for note in notes:
        print(note, file=sys.stderr)
    print(json.dumps(line), flush=True)
    print(json.dumps({"program_tracing": args.program_tracing} | reading), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
