"""Dynamic batching through ColonyOS generators (paper §3.4.4 applied).

Each inference request is a fire-and-forget ``pack``; the generator fires
a batched-inference workflow once ``queuesize`` requests accumulate (or
the timeout lapses). The serving executor materializes the batch, runs
the engine once, and publishes per-request results to CFS under
``/results/<request_id>`` — requesters poll the metadata plane. This is
the paper's "integration via fire-and-forget" pattern turned into a
dynamic-batching inference server.
"""

from __future__ import annotations

import json
import secrets
import time
from typing import Any

import numpy as np

from ..core import tracing
from ..core.client import Colonies
from ..core.errors import NotFoundError, TimeoutError_
from ..core.fs import CFSClient

RESULTS_LABEL = "/results"


class InferenceClient:
    """Submit prompts as packs; poll CFS for results."""

    def __init__(self, client: Colonies, cfs: CFSClient, colony: str, generatorid: str, prvkey: str):
        self.client = client
        self.cfs = cfs
        self.colony = colony
        self.generatorid = generatorid
        self.prvkey = prvkey

    def submit(self, prompt_tokens: list[int], max_new_tokens: int = 8) -> str:
        rid = secrets.token_hex(8)
        self.client.pack(
            self.generatorid,
            {"request_id": rid, "prompt": list(map(int, prompt_tokens)), "max_new_tokens": max_new_tokens},
            self.prvkey,
        )
        return rid

    def result(self, rid: str) -> list[int] | None:
        try:
            data = self.cfs.download_bytes(self.colony, RESULTS_LABEL, f"{rid}.json")
        except NotFoundError:
            return None
        return json.loads(data)["tokens"]

    def wait(self, rid: str, timeout: float = 30.0, poll: float = 0.05) -> list[int]:
        deadline = time.time() + timeout
        while time.time() < deadline:
            r = self.result(rid)
            if r is not None:
                return r
            time.sleep(poll)
        raise TimeoutError_(f"request {rid} timed out")


def make_batch_handler(engine, cfs: CFSClient, colony: str):
    """Executor handler for the generator-fired 'generate_batch' function."""

    def generate_batch(ctx, **kwargs) -> list[Any]:
        requests = kwargs.get("packed_args", [])
        if not requests:
            return [0]
        with tracing.span("colony.batch.build", requests=len(requests)):
            max_new = max(int(r.get("max_new_tokens", 8)) for r in requests)
            longest = max(len(r["prompt"]) for r in requests)
            vocab_pad = 0
            prompts = np.full((len(requests), longest), vocab_pad, np.int32)
            for i, r in enumerate(requests):
                p = r["prompt"]
                prompts[i, longest - len(p):] = p  # right-align
        out = engine.generate(prompts, max_new_tokens=max_new)
        with tracing.span("colony.batch.upload", files=len(requests)):
            for i, r in enumerate(requests):
                cfs.upload_bytes(
                    colony,
                    RESULTS_LABEL,
                    f"{r['request_id']}.json",
                    json.dumps({"tokens": out[i].tolist()}).encode(),
                )
        return [len(requests)]

    return generate_batch
