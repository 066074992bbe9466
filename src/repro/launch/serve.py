"""Serving launcher: boots a ServeExecutor (optionally from a trained CFS
run) plus the generator-based dynamic batcher, then runs a request load.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b --requests 8

Requests go client → generator → assign → ServeExecutor → ServeEngine →
CFS result. A batch process that fails ends the run with its error and a
non-zero exit instead of waiting out the request timeout.
"""

from __future__ import annotations

import argparse
import sys
import time
from dataclasses import dataclass
from typing import Any

TIMEOUT_S = 300.0  # per request, and the batch process's maxexectime


class ServeFailed(RuntimeError):
    """A batch process failed; the message holds the executor's error."""


@dataclass
class Served:
    prompts: list[list[int]]
    outputs: list[list[int]]  # generated tokens, in request order
    engine: Any  # the ServeEngine that answered (cfg, params, stats)
    warmup_s: dict[str, float]  # first-call seconds of prefill and decode
    seconds: float  # first submit to last result


def serve(arch: str = "stablelm-3b", variant: str = "smoke", requests: int = 8,
          batch_size: int = 4, prompt_len: int = 8, max_new_tokens: int = 8,
          max_len: int = 64, run: str | None = None) -> Served:
    """Serve ``requests`` random prompts of ``prompt_len`` tokens through
    the colony and return what came back. Raises :class:`ServeFailed` as
    soon as a batch process fails."""
    import numpy as np

    from repro.core import Colonies, Crypto, InProcTransport
    from repro.core.cluster import standalone_server
    from repro.core.errors import TimeoutError_
    from repro.core.fs import CFSClient, MemoryStorage
    from repro.runtime.jax_executor import ServeExecutor
    from repro.serve.batcher import InferenceClient

    server_prv, colony_prv = Crypto.prvkey(), Crypto.prvkey()
    server = standalone_server(Crypto.id(server_prv))
    server.start_background(failsafe_interval=0.1)
    worker = None
    try:
        client = Colonies(InProcTransport([server]))
        client.add_colony("serve", Crypto.id(colony_prv), server_prv)
        storage = MemoryStorage()
        worker = ServeExecutor(
            client, "serve", "serve-0", "tpu-serve", storage,
            colony_prvkey=colony_prv, arch=arch, variant=variant,
            max_len=max_len, run=run)
        engine = worker.engine
        warmup_s = engine.warmup(batch_size, prompt_len, max_new_tokens)
        worker.start(poll_timeout=0.2)
        wf = {"colonyname": "serve", "functionspecs": [
            {"nodename": "batch", "funcname": "generate_batch",
             "conditions": {"executortype": "tpu-serve", "dependencies": []},
             "maxexectime": int(TIMEOUT_S)}]}
        g = client.add_generator(
            {"colonyname": "serve", "name": "batcher", "queuesize": batch_size,
             "timeout": 2.0, "workflow": wf}, colony_prv)
        infc = InferenceClient(client, CFSClient(client, storage, colony_prv),
                               "serve", g["generatorid"], colony_prv)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, engine.cfg.vocab_size, prompt_len).tolist()
                   for _ in range(requests)]
        t0 = time.perf_counter()
        rids = [infc.submit(p, max_new_tokens=max_new_tokens) for p in prompts]
        results: dict[str, list[int]] = {}
        deadline = t0 + TIMEOUT_S
        while len(results) < len(rids):
            # One result poll per round: every RPC is signed and verified in
            # Python, and it competes for the interpreter with the executor
            # thread that drives the chip.
            rid = rids[len(results)]
            if (r := infc.result(rid)) is not None:
                results[rid] = r
                continue
            failed = client.get_processes("serve", colony_prv, state="failed")
            if worker.failed or failed:
                if not failed:  # the executor counts before its close lands
                    time.sleep(1.0)
                    failed = client.get_processes("serve", colony_prv, state="failed")
                errors = [e for p in failed for e in p.get("errors", [])]
                raise ServeFailed("batch process failed: " + (
                    "\n".join(errors) or f"{worker.failed} failure(s) on the executor"))
            if time.perf_counter() > deadline:
                raise TimeoutError_(f"{len(rids) - len(results)} requests unanswered "
                                    f"after {TIMEOUT_S}s")
            time.sleep(0.05)
        seconds = time.perf_counter() - t0
        return Served(prompts, [results[r] for r in rids], engine, warmup_s, seconds)
    finally:
        if worker is not None:
            worker.stop()
        server.stop()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--variant", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--run", default=None, help="CFS run to load a checkpoint from")
    args = ap.parse_args()

    from repro.launch.compile_cache import place_compile_cache

    place_compile_cache()
    try:
        served = serve(arch=args.arch, variant=args.variant, requests=args.requests,
                       batch_size=args.batch_size, prompt_len=args.prompt_len,
                       max_new_tokens=args.max_new_tokens, max_len=args.max_len,
                       run=args.run)
    except ServeFailed as e:
        print(e, file=sys.stderr)
        raise SystemExit(1)
    for i, out in enumerate(served.outputs):
        print(i, out)
    st = served.engine.stats
    print(f"{st['requests']} requests in {st['batches']} batches, "
          f"{st['tokens']} tokens, {served.seconds:.1f}s")


if __name__ == "__main__":
    main()
