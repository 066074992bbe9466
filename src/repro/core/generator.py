"""Generators — threshold-triggered workflow templates (paper §3.4.4).

Third-party systems integrate fire-and-forget: they send ``pack`` requests
carrying one input datum each. ``pack`` only *appends* to the generator's
arg bucket (no state manipulation → no synchronization, any replica can
serve it, exactly the paper's argument). The elected leader scans the
generator table and, when ``queuesize`` args have accumulated — or
``timeout`` elapsed since the first pending arg — drains the bucket and
submits the template workflow with the packed args attached.

This is also how the serving stack implements **dynamic batching**:
each inference request is a pack; the generator emits one batched
inference workflow per ``queuesize`` requests (serve/batcher.py).
"""

from __future__ import annotations

import secrets
from typing import Any, Callable

from . import tracing
from .database import Database
from .errors import NotFoundError, ValidationError
from .process import now_ns
from .spec import WorkflowSpec

PACKS_TABLE = "generator_packs"


class GeneratorExtension:
    def __init__(self, server) -> None:
        self.server = server
        self.db: Database = server.db
        server.extensions.append(self)

    def handlers(self) -> dict[str, Callable[[str, dict], Any]]:
        return {
            "addgenerator": self._h_add_generator,
            "getgenerators": self._h_get_generators,
            "removegenerator": self._h_remove_generator,
            "pack": self._h_pack,
        }

    def _h_add_generator(self, identity: str, payload: dict) -> dict:
        g = payload["generator"]
        colony = g.get("colonyname", "")
        self.server._require_member(identity, colony)
        wf = WorkflowSpec.from_dict(g.get("workflow", {}))
        if not wf.specs:
            raise ValidationError("generator needs a workflow template")
        for s in wf.specs:
            s.conditions.colonyname = s.conditions.colonyname or colony
        wf.colonyname = colony
        wf.validate()
        queuesize = int(g.get("queuesize", 1))
        if queuesize < 1:
            raise ValidationError("queuesize must be >= 1")
        entry = {
            "generatorid": secrets.token_hex(16),
            "colonyname": colony,
            "name": g.get("name", ""),
            "workflow": wf.to_dict(),
            "queuesize": queuesize,
            "timeout": float(g.get("timeout", 0)),  # seconds; 0 = only threshold
            "firstpack": 0,
            "runs": 0,
            "added": now_ns(),
        }
        self.db.generator_put(entry)
        return entry

    def _h_get_generators(self, identity: str, payload: dict) -> list[dict]:
        colony = payload["colonyname"]
        self.server._require_member(identity, colony)
        out = []
        for e in self.db.generator_list(colony):
            e["pending"] = self.db.kv_len(PACKS_TABLE, e["generatorid"])
            out.append(e)
        return out

    def _h_remove_generator(self, identity: str, payload: dict) -> dict:
        gid = payload["generatorid"]
        entry = self.db.generator_get(gid)
        if entry is None:
            raise NotFoundError("generator not found")
        self.server._require_member(identity, entry["colonyname"])
        self.db.generator_del(gid)
        self.db.kv_take_all(PACKS_TABLE, gid)
        return {"generatorid": gid, "removed": True}

    def _h_pack(self, identity: str, payload: dict) -> dict:
        """Append-only: safe on any replica without synchronization (§3.4.4)."""
        gid = payload["generatorid"]
        entry = self.db.generator_get(gid)
        if entry is None:
            raise NotFoundError("generator not found")
        self.server._require_member(identity, entry["colonyname"])
        n = self.db.kv_append(
            PACKS_TABLE, gid, {"arg": payload.get("arg"), "ts": now_ns()}
        )
        if entry.get("firstpack", 0) == 0:
            entry["firstpack"] = now_ns()
            self.db.generator_put(entry)
        return {"generatorid": gid, "pending": n}

    # -- leader scan --------------------------------------------------------
    def tick(self) -> int:
        ts = now_ns()
        fired = 0
        for entry in self.db.generator_all():
            gid = entry["generatorid"]
            pending = self.db.kv_len(PACKS_TABLE, gid)
            if pending == 0:
                continue
            timed_out = (
                entry.get("timeout", 0) > 0
                and entry.get("firstpack", 0) > 0
                and ts - entry["firstpack"] > entry["timeout"] * 1e9
            )
            if pending >= entry["queuesize"] or timed_out:
                self._fire(entry, ts, "full" if pending >= entry["queuesize"] else "timeout")
                fired += 1
        return fired

    def _fire(self, entry: dict, ts: int, reason: str) -> None:
        gid = entry["generatorid"]
        with tracing.span("colony.generator.fire", reason=reason) as sp:
            packs = self.db.kv_take_all(PACKS_TABLE, gid)
            if not packs:
                return
            args = [p["arg"] for p in packs]
            wf = WorkflowSpec.from_dict(entry["workflow"])
            # Packed args are delivered to the DAG roots via kwargs.
            for s in wf.specs:
                if not s.conditions.dependencies:
                    s.kwargs = dict(s.kwargs)
                    s.kwargs["packed_args"] = args
            procs = self.server.submit_workflow_processes(wf)
            entry = dict(entry)
            entry["firstpack"] = 0
            entry["runs"] = entry.get("runs", 0) + 1
            self.db.generator_put(entry)
            self.server._notify_queue()
            holds = [(ts - p["ts"]) / 1e9 for p in packs]
            sp.set(packs=len(packs), processid=procs[0].processid, oldest_hold_s=max(holds),
                   mean_hold_s=sum(holds) / len(holds))
        tracing.count(f"generator.fires.{reason}")
