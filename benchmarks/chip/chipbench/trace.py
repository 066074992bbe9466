"""From the profiler's trace to device time, idle time and their causes.

``extract`` reads an ``.xplane.pb`` with nothing but JAX: for each chip
in use, the programs it ran (the "XLA Modules" line of its device
plane) and the total time of each operation ("XLA Ops", named
``program/op``); from the host plane, the harness's own spans, whose
names start with ``bench.``. ``reduce`` turns that into the numbers the
metrics read. The two are apart so that a small recorded extract can be
checked by a test.

Busy time is the union of the intervals in which a program ran on the
chip; the window is the harness's ``bench.window`` span.
"""

from __future__ import annotations

import glob
import re
from collections import defaultdict

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_HASH = re.compile(r"\(\d+\)$")
# Control flow whose body's operations have events of their own.
_CONTAINERS = ("%while", "%conditional", "%call")


def program_name(name: str) -> str:
    """``jit_serve_step(1234...)`` -> ``jit_serve_step``."""
    return _HASH.sub("", name)


def _op_name(name: str) -> str:
    """``%fusion.1 = f32[4]{0:T(128)} fusion(...)`` -> ``%fusion.1 = f32[4]``."""
    return re.split(r"[{(]", name, maxsplit=1)[0].strip()[:120]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {paths}")
    return paths[0]


def extract(path: str, chips: int) -> dict:
    """{"programs": {chip: [[name, start_ns, dur_ns], ...]},
    "ops": {"program/op": ns}, "spans": [[name, start_ns, dur_ns], ...]}"""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    spans: list = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, e.start_ns, e.duration_ns])
    windows = [sp for sp in spans if sp[0] == WINDOW_SPAN]
    w0 = min((sp[1] for sp in windows), default=float("-inf"))
    w1 = max((sp[1] + sp[2] for sp in windows), default=float("inf"))
    programs: dict[str, list] = {}
    ops: dict[str, float] = defaultdict(float)
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        chip = int(plane.name.rsplit(":", 1)[1])
        if chip >= chips:
            continue
        lines = {line.name: line for line in plane.lines}
        mods = [[program_name(e.name), e.start_ns, e.duration_ns]
                for e in lines["XLA Modules"].events] if "XLA Modules" in lines else []
        programs[str(chip)] = mods
        if chip == 0 and "XLA Ops" in lines:
            j = 0
            for e in lines["XLA Ops"].events:  # both lines are in time order
                start = e.start_ns
                if not w0 <= start < w1:
                    continue
                while j + 1 < len(mods) and mods[j][1] + mods[j][2] <= start:
                    j += 1
                name = _op_name(e.name)
                if name.startswith(_CONTAINERS):
                    continue
                owner = mods[j][0] if mods and mods[j][1] <= start else "?"
                ops[f"{owner}/{name}"] += e.duration_ns
    return {"programs": programs, "ops": dict(ops), "spans": spans}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _label(t: float, spans: list, before: str, after: str) -> str:
    inner = [sp for sp in spans if sp[0] != WINDOW_SPAN and sp[1] <= t <= sp[1] + sp[2]]
    host = min(inner, key=lambda sp: sp[2])[0] if inner else "outside engine.generate"
    return f"{host}: {before} -> {after}"


def reduce(ex: dict) -> dict:
    """busy_s (mean over chips), window_s, each program's call durations
    on chip 0 (seconds), the operations that took most time, and the
    idle gaps on chip 0 summed by what the host was doing."""
    windows = [sp for sp in ex["spans"] if sp[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w0 = min(sp[1] for sp in windows)
    w1 = max(sp[1] + sp[2] for sp in windows)
    busy = []
    for chip, mods in ex["programs"].items():
        clipped = [(max(s, w0), min(s + d, w1)) for _, s, d in mods if s < w1 and s + d > w0]
        busy.append(sum(e - s for s, e in _union(clipped)))
    calls: dict[str, list[float]] = defaultdict(list)
    mods0 = sorted((m for m in ex["programs"].get("0", []) if w0 <= m[1] < w1),
                   key=lambda m: m[1])
    for name, _, d in mods0:
        calls[name].append(d / 1e9)
    gaps: dict[str, float] = defaultdict(float)
    prev_end, prev_name = w0, "window open"
    for name, s, d in mods0 + [["window close", w1, 0.0]]:
        if s > prev_end:
            gaps[_label((prev_end + s) / 2, ex["spans"], prev_name, name)] += (s - prev_end) / 1e9
        if s + d >= prev_end:
            prev_end, prev_name = s + d, name
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9 if busy else 0.0,
        "window_s": (w1 - w0) / 1e9,
        "calls": dict(calls),
        "device_ops": top({k: v / 1e9 for k, v in ex["ops"].items()}),
        "idle_gaps": top(gaps),
    }
