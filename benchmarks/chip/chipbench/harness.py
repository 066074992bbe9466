"""One run of one cell: set-up, the measured window, the check, the line.

Set-up makes the weights on the device from the seed, starts the colony
and the executor, and warms the engine at each batch size from 1 to the
generator's ``queuesize + 2`` at the mix's prompt length. The engine's
generate ends in an eager concatenation of its output tokens, which
builds a small program for each batch size and output length it meets;
those are left to the program, as it is deployed, and the window counts
them (``compiles_in_window``). Then the load generator, a child process
without JAX, offers the mix for ``seconds`` seconds, open loop, and
waits for every request due in that time (at most the mix's ``drain_s``
past the close).
"""

from __future__ import annotations

import gc
import json
import os
import statistics
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable

from . import check, system, trace as trace_mod, traffic as traffic_mod, weights
from .cell import HERE, ROOT, Cell, reader
from .peaks import peaks_for

CHILD_GRACE_S = 60.0


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Run:
    """What one window left behind; the per-layer metrics read it."""

    dims: weights.Dims
    peaks: dict | None
    seconds: float
    t0_wall: float
    end_wall: float
    records: list[dict]  # one per request due in the window (loadgen.py)
    processes: list[dict]  # the batch processes, as the broker records them
    calls: list[dict]  # engine.generate calls: wall start/end, batch, seq, new
    compiles_in_window: int
    memory_peak_bytes: int | None
    trace: dict | None  # trace.reduce(...) of a traced window


class CompileCounter:
    """Counts the programs JAX builds (compiled, or loaded from the
    persistent cache) while it is on."""

    _registered = False
    _active: list["CompileCounter"] = []

    def __init__(self) -> None:
        import jax

        self.count = 0
        if not CompileCounter._registered:
            jax.monitoring.register_event_duration_secs_listener(CompileCounter._on_event)
            CompileCounter._registered = True

    @staticmethod
    def _on_event(event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            for c in CompileCounter._active:
                c.count += 1

    def __enter__(self) -> "CompileCounter":
        CompileCounter._active.append(self)
        return self

    def __exit__(self, *exc) -> None:
        CompileCounter._active.remove(self)


class StallWatch:
    """A thread that wakes every ``period`` seconds and records each wake
    that came more than ``least`` seconds late: the times that no thread
    of this process could run Python (the interpreter lock held, or the
    process descheduled). It tells a stall of the whole serving process
    from one of a single request, and records the garbage collector's
    pauses beside it, one suspect of such a stall."""

    def __init__(self, period: float = 0.02, least: float = 0.2) -> None:
        self.period, self.least = period, least
        self.stalls: list[tuple[float, float]] = []  # (wall time it began, seconds)
        self.gc_pauses: list[tuple[float, float, int]] = []  # (wall, seconds, generation)
        self._gc_start = 0.0
        gc.callbacks.append(self._on_gc)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_start = time.monotonic()
        elif (took := time.monotonic() - self._gc_start) > 0.05:
            self.gc_pauses.append((time.time() - took, took, info["generation"]))

    def _run(self) -> None:
        last = time.monotonic()
        while not self._stop.wait(self.period):
            now = time.monotonic()
            if now - last - self.period > self.least:
                self.stalls.append((time.time() - (now - last), now - last - self.period))
            last = now

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)


def _instrument(engine, calls: list[dict]) -> None:
    """Record every generate call, inside a host span of its own."""
    import jax

    inner = engine.generate

    def generate(tokens, max_new_tokens=16, *args, **kw):
        start = time.time()
        with jax.profiler.TraceAnnotation("bench.engine_generate"):
            out = inner(tokens, max_new_tokens, *args, **kw)
        calls.append({"start": start, "end": time.time(), "batch": int(tokens.shape[0]),
                      "seq": int(tokens.shape[1]), "new": int(max_new_tokens)})
        return out

    engine.generate = generate


def warm(engine, traffic: dict) -> None:
    """Compile (or load from the cache) prefill and decode at every batch
    size the generator can form."""
    import jax

    plen = int(traffic["prompt_tokens"])
    for b in range(1, int(traffic["generator"]["queuesize"]) + 3):
        with jax.profiler.TraceAnnotation("bench.warmup"):
            engine.warmup(b, plen, 2)


class LoadGen:
    """The child process that offers the traffic (loadgen.py)."""

    def __init__(self, spec: dict, workdir: str) -> None:
        path = Path(workdir) / "loadgen.json"
        path.write_text(json.dumps(spec))
        self.proc = subprocess.Popen(
            [sys.executable, str(HERE / "chipbench" / "loadgen.py"), str(path)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, cwd=str(ROOT))
        self._lock = threading.Lock()
        self._out: list[str] = []
        self._reader: threading.Thread | None = None

    def wait_ready(self) -> None:
        line = self.proc.stdout.readline().strip()
        if line != "ready":
            self.proc.kill()
            raise RuntimeError(f"load generator did not start (said {line!r})")
        self._reader = threading.Thread(target=lambda: self._out.append(self.proc.stdout.read()),
                                        daemon=True)
        self._reader.start()

    def send(self, line: str) -> None:
        with self._lock:
            if self.proc.poll() is None:
                try:
                    self.proc.stdin.write(line + "\n")
                    self.proc.stdin.flush()
                except BrokenPipeError:
                    pass

    def result(self, timeout: float) -> dict:
        try:
            self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
            raise RuntimeError("load generator did not finish in time") from None
        self._reader.join(timeout=10)
        if self.proc.returncode != 0:
            raise RuntimeError(f"load generator exited with {self.proc.returncode}")
        return json.loads("".join(self._out).strip().splitlines()[-1])

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        for f in (self.proc.stdin, self.proc.stdout):
            try:
                f.close()
            except OSError:
                pass


def percentile(records: list[dict], end_wall: float, t0_wall: float, p: float) -> float:
    """Nearest-rank percentile of latency over every request attempted.
    A request that failed or was never answered ranks above every answered
    one; if the rank falls on one, its value is the wait it had when the
    run ended (a floor on a latency that never came)."""
    answered = sorted(r["latency"] for r in records if r["latency"] is not None)
    missed = sorted(end_wall - (t0_wall + r["due"]) for r in records if r["latency"] is None)
    ranked = answered + missed
    k = max(1, -(-len(ranked) * p // 100))  # ceil, at least rank 1
    return float(ranked[int(k) - 1])


def output_tokens_per_s(records: list[dict], t0_wall: float) -> float:
    """Tokens asked for by the answered requests, over the seconds from
    the window's opening to the last answer: all the work and all the
    time it took. Padded tokens (a batch decodes to its longest request)
    do not count."""
    done = [r for r in records if r["tokens"] is not None and not r["failed"]]
    if not done:
        return 0.0
    return sum(r["max_new"] for r in done) / (max(r["recv_wall"] for r in done) - t0_wall)


def end_to_end(run: Run, setup_s: float) -> dict[str, float]:
    return {
        "latency_p50_s": percentile(run.records, run.end_wall, run.t0_wall, 50),
        "output_tokens_per_s": output_tokens_per_s(run.records, run.t0_wall),
        "setup_s": setup_s,
    }


def _free(tree) -> None:
    import jax

    for leaf in jax.tree.leaves(tree):
        leaf.delete()


class Session:
    """The system under test, set up once: colony, weights, executor,
    every program warmed. ``window`` offers traffic to it; the
    calibration and the knee sweep reuse one session for many windows."""

    def __init__(self, cell: Cell, seed: int, variant: str, workdir: str) -> None:
        import jax

        self.cell, self.variant, self.workdir = cell, variant, workdir
        self.mc = system.model_config(cell.config, variant)
        self.dims = system.dims_of(self.mc)
        if variant == "full" and self.dims != weights.Dims.from_config(cell.config):
            raise ValueError(f"the program builds {self.dims}, the configuration file "
                             f"states {weights.Dims.from_config(cell.config)}")
        self.dtype = jax.numpy.dtype(self.mc.param_dtype)
        self.calls: list[dict] = []
        self.phases: dict[str, float] = {}  # set-up seconds by phase
        t = time.time()
        self.colony = system.Colony(os.path.join(workdir, "blobs"), cell.traffic)
        try:
            params = self.make_params(seed)
            jax.block_until_ready(params)
            self.phases["weights"] = time.time() - t
            self.worker = self.colony.start_executor(
                self.mc.name, variant, params, int(cell.traffic["max_len"]))
            self.engine = self.worker.engine
            _instrument(self.engine, self.calls)
            t = time.time()
            warm(self.engine, cell.traffic)
            self.phases["warm-up"] = time.time() - t
            self.worker.start(poll_timeout=0.2)
        except BaseException:
            self.colony.stop()
            raise

    def make_params(self, seed: int) -> dict:
        w = weights.make(self.dims, traffic_mod.seed32(seed, traffic_mod.STREAM_WEIGHTS),
                         self.dtype)
        return system.program_params(w, self.dims, self.mc)

    def set_weights(self, seed: int) -> None:
        """Serve the weights of another seed (the programs stay warm)."""
        self.free_weights()
        self.engine.params = self.make_params(seed)

    def free_weights(self) -> None:
        if self.engine.params is not None:
            _free(self.engine.params)
            self.engine.params = None

    def window(self, traffic: dict, seed: int, seconds: float,
               trace_dir: str | None = None) -> dict:
        """Offer ``traffic`` for ``seconds`` and wait for its answers.
        Returns the load generator's records with what the broker and the
        engine recorded meanwhile."""
        import jax

        first_call = len(self.calls)
        t = time.time()
        gen = LoadGen({
            "src": str(ROOT / "src"), "bench": str(HERE), "host": self.colony.http.host,
            "port": self.colony.http.port, "colony": system.COLONY,
            "generatorid": self.colony.generatorid, "prvkey": self.colony.colony_prv,
            "storage": self.colony.storage_dir, "traffic": traffic, "seed": seed,
            "seconds": seconds, "vocab": self.dims.vocab, "drain_s": float(traffic["drain_s"]),
        }, self.workdir)
        watch = stalls = None
        try:
            gen.wait_ready()
            self.phases["load generator"] = time.time() - t
            if trace_dir is not None:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            t0 = time.time() + 0.05
            stalls = StallWatch()
            with CompileCounter() as compiles, jax.profiler.TraceAnnotation("bench.window"):
                watch = system.FailureWatch(self.colony, lambda rid: gen.send(f"fail {rid}"))
                gen.send(f"go {t0!r}")
                out = gen.result(seconds + float(traffic["drain_s"]) + CHILD_GRACE_S)
                end_wall = time.time()
            stalls.stop()
            if trace_dir is not None:
                jax.profiler.stop_trace()
            watch.stop()
        finally:
            for thread in (watch, stalls):
                if thread is not None:
                    thread.stop()
            gen.close()
        if out.get("jax_imported"):
            raise RuntimeError("the load generator's process imported JAX")
        return {"records": out["records"], "t0": t0, "end_wall": end_wall,
                "compiles": compiles.count, "calls": self.calls[first_call:],
                "stalls": stalls.stalls, "gc_pauses": stalls.gc_pauses,
                "processes": [p for p in self.colony.processes()
                              if p["submissiontime"] / 1e9 >= t0 - 1.0]}

    def close(self) -> None:
        self.colony.stop()


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *, t_start: float,
             variant: str = "full", require_chip: bool = True,
             fault: Callable | None = None) -> tuple[dict, list[str]]:
    """Run ``cell`` once. Returns the result line's object and the lines
    of the comparison (number, limit) for the end of standard error.
    ``fault``, for the tests, breaks the engine after set-up."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_chip and (platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(f"this cell needs {cell.chips} TPU chip(s); JAX found "
                     f"{len(devices)} {platform} device(s)")
    from repro.launch.compile_cache import place_compile_cache

    place_compile_cache()
    traffic = cell.traffic
    with tempfile.TemporaryDirectory(prefix="chipbench-") as tmp:
        session = Session(cell, seed, variant, tmp)
        try:
            if fault is not None:
                fault(session.engine)
            trace_dir = os.path.join(tmp, "trace") if traced else None
            win = session.window(traffic, seed, seconds, trace_dir)
            stats = devices[0].memory_stats() or {}
        finally:
            session.close()
        session.free_weights()
        t = time.time()
        tr = trace_mod.reduce(trace_mod.extract(trace_mod.find_xplane(trace_dir), cell.chips)) \
            if traced else None
        after = {"trace reduction": time.time() - t}
        t = time.time()
        verdict = verify(session, win["records"], seed, seconds)
        after["check"] = time.time() - t

    run = Run(dims=session.dims,
              peaks=peaks_for(devices[0].device_kind) if platform == "tpu" else None,
              seconds=seconds, t0_wall=win["t0"], end_wall=win["end_wall"],
              records=win["records"], processes=win["processes"], calls=win["calls"],
              compiles_in_window=win["compiles"],
              memory_peak_bytes=stats.get("peak_bytes_in_use"), trace=tr)
    recs = run.records
    failed = sum(r["failed"] for r in recs)
    unanswered = sum(r["tokens"] is None and not r["failed"] for r in recs)
    limit = float(cell.config["check"]["max_gap"]["limit"])
    checked = {
        "max_gap": {"value": verdict["max_gap"], "limit": limit},
        "failed": {"value": failed, "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
        "short_answers": {"value": verdict["short"], "limit": 0},
    }
    correct = (verdict["max_gap"] <= limit and failed == 0 and unanswered == 0
               and verdict["short"] == 0 and verdict["tokens"] >= 1)
    if traced:
        metrics = {}
        for m in cell.per_layer:
            value = reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = end_to_end(run, win["t0"] - t_start)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = {"platform": platform, "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    line = {"correct": bool(correct), "attempted": len(recs), "failed": failed + unanswered,
            "metrics": metrics, "device": device}
    if traced:
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    line["checked"] = checked
    lags = [r["lag"] for r in recs if r["lag"] is not None]
    notes = [
        "set-up: " + ", ".join(f"{k} {v:.3f} s" for k, v in session.phases.items()),
        "after the window: " + ", ".join(f"{k} {v:.3f} s" for k, v in after.items()),
        f"requests: attempted {len(recs)} answered {len(recs) - failed - unanswered} "
        f"failed {failed} unanswered {unanswered}; sender lag median "
        f"{statistics.median(lags) if lags else 0.0!r} s max {max(lags) if lags else 0.0!r} s",
        _stall_note(recs, win["stalls"], win["gc_pauses"], run.calls, run.t0_wall),
        f"compared {verdict['requests']} requests, {verdict['tokens']} served tokens",
    ] + [f"check {k}: {v['value']!r} limit {v['limit']!r}" for k, v in checked.items()]
    return line, notes


def _stall_note(records: list[dict], stalls: list, gc_pauses: list, calls: list[dict],
                t0: float) -> str:
    """The slowest request submission against the serving process's
    stalls and collector pauses: when each came, and whether the engine
    was generating then."""
    def where(wall: float) -> str:
        busy = any(c["start"] <= wall <= c["end"] for c in calls)
        return f"at {wall - t0:.3f} s ({'inside' if busy else 'outside'} engine.generate)"

    sub = [r for r in records if r.get("submit_s") is not None]
    slow = max(sub, key=lambda r: r["submit_s"]) if sub else None
    worst = max(stalls, key=lambda st: st[1]) if stalls else None
    return ("stalls: slowest submit " + (f"{slow['submit_s']!r} s {where(slow['sent_wall'])}"
                                         if slow else "none")
            + f"; serving process stalled {len(stalls)} times over 0.2 s"
            + (f", longest {worst[1]!r} s {where(worst[0])}" if worst else "")
            + f"; {len(gc_pauses)} collector pauses over 0.05 s"
            + (f", longest {gcw[1]!r} s (generation {gcw[2]}) {where(gcw[0])}"
               if (gcw := max(gc_pauses, key=lambda g: g[1], default=None)) else ""))


def verify(session: Session, records: list[dict], seed: int, seconds: float,
           control: bool = False) -> dict:
    """The reference over a sample of the window's answers (the program's
    weights must be freed first: the reference makes its own)."""
    traffic, dims = session.cell.traffic, session.dims
    requests = traffic_mod.schedule(traffic, seed, seconds, dims.vocab)
    picked = check.sample(records, seed, int(traffic["check"]["tokens"]))
    pad_to = int(traffic["prompt_tokens"]) + int(traffic["output_tokens"]["max"]) - 1
    w = weights.make(dims, traffic_mod.seed32(seed, traffic_mod.STREAM_WEIGHTS), session.dtype)
    try:
        return check.compare(w, dims, requests, picked, pad_to,
                             int(traffic["check"]["rows_per_call"]), control=control)
    finally:
        _free(w)
