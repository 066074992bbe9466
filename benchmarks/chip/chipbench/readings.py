"""What the per-layer metric readers share: the broker's batch records
joined to the requests, and the calls each engine.generate made."""

from __future__ import annotations

from . import work

PREFILL_PROGRAM = "jit_prefill_fn"  # the engine's jitted prefill
DECODE_PROGRAM = "jit_serve_step"  # the engine's jitted decode step


def batches(run) -> list[dict]:
    """Successful batch processes: their request ids and broker times (s)."""
    out = []
    for p in run.processes:
        if p["state"] != "successful":
            continue
        args = p["spec"].get("kwargs", {}).get("packed_args", [])
        out.append({"rids": [a["request_id"] for a in args],
                    "submitted": p["submissiontime"] / 1e9, "started": p["starttime"] / 1e9,
                    "ended": p["endtime"] / 1e9})
    return out


def batch_of(run) -> dict[str, dict]:
    return {rid: b for b in batches(run) for rid in b["rids"]}


def call_of(run, batch: dict) -> dict | None:
    """The engine.generate call a batch process made."""
    return next((c for c in run.calls if batch["started"] <= c["start"] <= batch["ended"]), None)


def model_calls(run, kind: str | None = None) -> list[tuple[str, int, int]]:
    """(kind, batch, seq or pos) of every prefill and decode call the
    window's generate calls made."""
    calls = [c for g in run.calls for c in work.generate_calls(g["batch"], g["seq"], g["new"])]
    return [c for c in calls if kind is None or c[0] == kind]


def mean(values: list[float]) -> float | None:
    return sum(values) / len(values) if values else None


def roofline_share(run, kind: str, program: str) -> float | None:
    """Mean least time the chip could take for this kind of call, over the
    mean device time of its program's runs in the trace, in percent."""
    if run.trace is None or run.peaks is None:
        return None
    device = mean(run.trace["calls"].get(program, []))
    least = mean([work.roofline_s(run.dims, k, b, n, run.peaks)
                  for k, b, n in model_calls(run, kind)])
    return None if device is None or least is None else 100.0 * least / device


def mfu(run) -> float | None:
    """The operations the window's prefill and decode calls need, over the
    traced window's seconds times the chip's peak bf16 rate, in percent."""
    if run.trace is None or run.peaks is None or not run.calls:
        return None
    ops = sum(work.flops(run.dims, k, b, n) for k, b, n in model_calls(run))
    return 100.0 * ops / (run.trace["window_s"] * run.peaks["bf16_flops_per_s"])
