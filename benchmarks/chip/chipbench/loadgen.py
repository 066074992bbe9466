"""The load generator and requester: a process of its own, without JAX.

It reaches the colony as a remote user would, over the HTTP transport,
signing every RPC in its own interpreter, so its signing never takes the
interpreter of the process that drives the chip.

    python loadgen.py <spec.json>

Protocol with the parent: the child prints ``ready`` once it has
connected, then reads ``go <t0>`` (the window's opening, wall clock) from
standard input, and later ``fail <request id>`` for each request whose
batch failed. It sends at the due times, polls as ``launch/serve.py``
does (the oldest outstanding request first, straight on after a hit, 50
ms sleep after a miss), and prints one JSON object as its last line.
"""

from __future__ import annotations

import json
import statistics
import sys
import threading
import time
from collections import deque
from pathlib import Path

POLL_SLEEP_S = 0.05


def main(spec_path: str) -> None:
    spec = json.loads(Path(spec_path).read_text())
    sys.path[:0] = [spec["src"], spec["bench"]]
    from chipbench.traffic import schedule
    from repro.core.client import Colonies
    from repro.core.fs import CFSClient, LocalStorage
    from repro.core.http_transport import HttpTransport
    from repro.serve.batcher import InferenceClient

    client = Colonies(HttpTransport(spec["host"], spec["port"]))
    cfs = CFSClient(client, LocalStorage(spec["storage"]), spec["prvkey"])
    infc = InferenceClient(client, cfs, spec["colony"], spec["generatorid"], spec["prvkey"])
    requests = schedule(spec["traffic"], spec["seed"], spec["seconds"], spec["vocab"])
    print("ready", flush=True)

    line = sys.stdin.readline().split()
    if not line or line[0] != "go":
        raise SystemExit("loadgen: expected 'go <t0>' on standard input")
    t0 = time.monotonic() + (float(line[1]) - time.time())
    deadline = t0 + spec["seconds"] + spec["drain_s"]

    recs = [{"i": r.index, "due": r.due_s, "max_new": r.max_new_tokens, "rid": None,
             "lag": None, "sent_wall": None, "submit_s": None, "latency": None,
             "recv_wall": None, "tokens": None, "failed": False} for r in requests]
    lock = threading.Lock()
    outstanding: deque[int] = deque()
    rid_index: dict[str, int] = {}
    failed_rids: set[str] = set()
    sender_done = threading.Event()
    counts = {"polls": 0, "poll_errors": 0}

    def send() -> None:
        for r in requests:
            target = t0 + r.due_s
            if (delay := target - time.monotonic()) > 0:
                time.sleep(delay)
            rec = recs[r.index]
            rec["lag"] = time.monotonic() - target
            rec["sent_wall"] = time.time()
            start = time.monotonic()
            try:
                rid = infc.submit(r.prompt.tolist(), max_new_tokens=r.max_new_tokens)
            except Exception as e:  # noqa: BLE001 — a refused request is a miss
                rec["failed"] = True
                print(f"loadgen: request {r.index} refused: {e}", file=sys.stderr)
                continue
            finally:
                rec["submit_s"] = time.monotonic() - start
            with lock:
                rec["rid"] = rid
                rid_index[rid] = r.index
                outstanding.append(r.index)
        sender_done.set()

    def request_results() -> None:
        while time.monotonic() < deadline:
            with lock:
                idx = outstanding[0] if outstanding else None
                rid = recs[idx]["rid"] if idx is not None else None
                if rid in failed_rids:
                    recs[idx]["failed"] = True
                    outstanding.popleft()
                    continue
            if idx is None:
                if sender_done.is_set():
                    return
                time.sleep(0.005)
                continue
            counts["polls"] += 1
            try:
                tokens = infc.result(rid)
            except Exception as e:  # noqa: BLE001 — keep polling; count it
                counts["poll_errors"] += 1
                print(f"loadgen: poll of request {idx} failed: {e}", file=sys.stderr)
                tokens = None
            if tokens is not None:
                now = time.monotonic()
                with lock:
                    rec = recs[idx]
                    rec["latency"] = now - (t0 + rec["due"])
                    rec["recv_wall"] = time.time()
                    rec["tokens"] = tokens
                    outstanding.popleft()
                continue
            time.sleep(POLL_SLEEP_S)

    def read_failures() -> None:
        for msg in sys.stdin:
            parts = msg.split()
            if len(parts) == 2 and parts[0] == "fail":
                with lock:
                    failed_rids.add(parts[1])

    threading.Thread(target=read_failures, daemon=True).start()
    sender = threading.Thread(target=send)
    sender.start()
    request_results()
    sender.join()

    lags = [r["lag"] for r in recs if r["lag"] is not None]
    answered = sum(r["tokens"] is not None for r in recs)
    failed = sum(r["failed"] for r in recs)
    print(f"loadgen: sent {sum(r['rid'] is not None for r in recs)} answered {answered} "
          f"failed {failed} unanswered {len(recs) - answered - failed} of {len(recs)}; "
          f"sender lag median {statistics.median(lags) if lags else 0.0:.6f} s "
          f"max {max(lags) if lags else 0.0:.6f} s; polls {counts['polls']} "
          f"(errors {counts['poll_errors']})", file=sys.stderr, flush=True)
    print(json.dumps({"records": recs, "jax_imported": "jax" in sys.modules, **counts}),
          flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
