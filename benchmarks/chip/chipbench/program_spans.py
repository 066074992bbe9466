"""The program's own spans (``repro.core.tracing``) set against the chip's
busy and idle time.

The program records its spans while the JAX profiler captures, so a
traced window leaves them in the serving process. ``recorded(run)``
hands them to the per-layer readers. Those readers work on the host's
wall clock: they put the executor thread's spans against the traced
window's busy time, and never see when the chip was idle. That holds
because every program the chip runs in the window is launched and
waited for inside ``colony.engine.generate`` (its collect fetches the
tokens to the host): the chip is then idle whenever the executor's
thread is outside that span, and idle inside it for the span's time
less the busy time. The three idle shares (no batch: the executor
parked in the broker's assign; executor; engine) sum to
``device_idle_share`` by that arithmetic. When device work outlasts
the span (busy time above its coverage) the split does not hold, and
the readers give nothing.

``extract`` and ``attribute`` read the chip's idle gaps themselves, on
the profiler's clock, from an ``.xplane.pb``: the colony spans'
annotations on the host plane, each on its thread's line, against the
programs on chip 0. Each idle gap is labelled by the innermost span open
on the thread that ran ``engine.generate``, never by a span of another
thread (the HTTP threads' RPCs would otherwise name the executor's
gaps). ``attribute.py`` prints that breakdown beside the readers' shares
of the same window; a recorded extract from the chip tests both.

On a program without these spans every function here returns None.
"""

from __future__ import annotations

from collections import Counter

PREFIX = "colony."
GENERATE = "colony.engine.generate"
ASSIGN_WAIT = "colony.broker.assign_wait"
FIRE = "colony.generator.fire"
COMPILE = "colony.jax.compile"
SIGNATURES = ("colony.rpc.sign", "colony.rpc.verify")
NO_SPAN = "(no colony span)"

last: tuple = (None, None)  # (run, its spans): six readers, one drain


def recorded(run) -> list[dict] | None:
    """The spans the program recorded in this run's traced window."""
    global last
    if run.trace is None:
        return None
    if last[0] is not run:
        try:
            import repro.core.tracing as tracing
        except ImportError:  # a program from before the spans
            return None
        last = (run, tracing.drain()["spans"])
    return last[1] or None


def window(run) -> tuple[float, float]:
    """The traced window on the wall clock: it closes when the run's
    ``end_wall`` is taken."""
    return run.end_wall - run.trace["window_s"], run.end_wall


def covered(intervals: list[tuple[float, float]], lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside [lo, hi]."""
    total, reach = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, reach), min(e, hi)
        if e > s:
            total += e - s
            reach = e
    return total


def executor_thread(spans: list[dict]):
    """The thread that ran most ``engine.generate`` calls."""
    threads = Counter(s["thread"] for s in spans if s["name"] == GENERATE)
    return threads.most_common(1)[0][0] if threads else None


def idle_shares(run, spans: list[dict] | None = None) -> dict[str, float] | None:
    """The traced window's idle share in three: the executor parked in
    the broker's assign (``no_batch``), inside ``engine.generate``
    (``engine``) and the rest of the executor's time (``executor``).
    None where a share comes out negative: the chip was busy for longer
    than ``engine.generate`` was open, so some device work ran outside
    it and the split would be wrong. ``spans``: the run's own unless
    given."""
    spans = recorded(run) if spans is None else spans
    if spans is None or run.trace is None or run.trace["window_s"] <= 0:
        return None
    thread = executor_thread(spans)
    if thread is None:
        return None
    lo, hi = window(run)
    mine = lambda name: [(s["start"], s["end"]) for s in spans
                         if s["name"] == name and s["thread"] == thread]
    w, busy = run.trace["window_s"], run.trace["busy_s"]
    generate = covered(mine(GENERATE), lo, hi)
    wait = covered(mine(ASSIGN_WAIT), lo, hi)
    shares = {"no_batch": wait / w, "engine": (generate - busy) / w,
              "executor": (w - generate - wait) / w}
    return None if min(shares.values()) < 0 else shares


def span_seconds(run, names: tuple[str, ...]) -> float | None:
    """Seconds of the named spans inside the window, all threads summed."""
    spans = recorded(run)
    if spans is None:
        return None
    lo, hi = window(run)
    return sum(max(0.0, min(s["end"], hi) - max(s["start"], lo))
               for s in spans if s["name"] in names)


def cpu_seconds(run, names: tuple[str, ...]) -> float | None:
    """CPU seconds (the ``cpu_s`` of each span, its thread's own time) of
    the named spans that began inside the window, all threads summed."""
    spans = recorded(run)
    if spans is None:
        return None
    lo, hi = window(run)
    return sum(s["attrs"].get("cpu_s", 0.0) for s in spans
               if s["name"] in names and lo <= s["start"] < hi)


def mean_hold(run) -> float | None:
    """Mean seconds a pack waited in the generator, over the packs of
    the batches fired in the window."""
    spans = recorded(run)
    if spans is None:
        return None
    lo, hi = window(run)
    fires = [s["attrs"] for s in spans
             if s["name"] == FIRE and lo <= s["start"] < hi and "packs" in s["attrs"]]
    packs = sum(f["packs"] for f in fires)
    return sum(f["mean_hold_s"] * f["packs"] for f in fires) / packs if packs else None


def extract(path: str) -> list[list]:
    """[name, start_ns, duration_ns, thread] of every colony span in the
    profiler's host plane; a thread is its line's index there."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out += [[e.name, e.start_ns, e.duration_ns, i] for e in line.events
                        if e.name.startswith(PREFIX)]
    return out


def _innermost(spans: list[list]) -> list[tuple[float, float, str, bool, bool]]:
    """One thread's spans (properly nested) as consecutive pieces:
    (start, end, innermost name, inside generate, inside assign_wait)."""
    pieces: list = []
    stack: list = []  # (end, name, in generate, in wait)
    t = float("-inf")

    def close_until(until: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= until:
            end, name, gen, wait = stack.pop()
            if end > t:
                pieces.append((t, end, name, gen, wait))
                t = end

    for name, s, d, _ in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        close_until(s)
        if stack and s > t:
            pieces.append((t, s) + stack[-1][1:])
        t = max(t, s)
        gen = name == GENERATE or bool(stack and stack[-1][2])
        wait = name == ASSIGN_WAIT or bool(stack and stack[-1][3])
        stack.append((s + d, name, gen, wait))
    close_until(float("inf"))
    return pieces


def attribute(programs: list[list], spans: list[list], w0: float, w1: float) -> dict | None:
    """Chip 0's idle time in [w0, w1] (profiler nanoseconds) by the
    innermost colony span on the executor's thread, and its three
    shares. ``programs``: [name, start, duration] on chip 0."""
    thread = executor_thread([{"name": sp[0], "thread": sp[3]} for sp in spans])
    if thread is None or w1 <= w0:
        return None
    busy = sorted((max(s, w0), min(s + d, w1)) for _, s, d in programs if s < w1 and s + d > w0)
    idle, reach = [], w0
    for s, e in busy:
        if s > reach:
            idle.append((reach, s))
        reach = max(reach, e)
    if reach < w1:
        idle.append((reach, w1))
    pieces = _innermost([sp for sp in spans if sp[3] == thread])
    gaps: dict[str, float] = Counter()
    shares = {"no_batch": 0.0, "executor": 0.0, "engine": 0.0}
    j = 0
    for a, b in idle:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        t, k = a, j
        while t < b:
            piece = pieces[k] if k < len(pieces) else (b, b, NO_SPAN, False, False)
            if piece[0] > t:  # before the next span: none open
                end, name, gen, wait = min(piece[0], b), NO_SPAN, False, False
            else:
                end, name, gen, wait = min(piece[1], b), piece[2], piece[3], piece[4]
                k += 1
            gaps[name] += (end - t) / 1e9
            shares["engine" if gen else "no_batch" if wait else "executor"] += (end - t) / 1e9
            t = end
    window_s = (w1 - w0) / 1e9
    idle_s = sum(b - a for a, b in idle) / 1e9
    return {"window_s": window_s, "idle_share": idle_s / window_s,
            "shares": {k: v / window_s for k, v in shares.items()},
            "idle_gaps": [[k, v] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])],
            "labelled_share": 1 - gaps.get(NO_SPAN, 0.0) / idle_s if idle_s else 1.0}
