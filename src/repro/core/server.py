"""The Colonies server — stateless broker at the heart of ColonyOS (paper §3).

Every request alters or reads database state; no session data lives in
memory between requests (§3.4.3), so any replica can serve any request —
except ``assign``, the single synchronized operation (§3.4.1), which in
HA deployments is serialized through the Raft leader (see cluster.py).

Responsibilities implemented here:
  * process submission / assignment / close (Tables 1–2, Fig. 2)
  * the Eq. (1) priority queue via the database backends
  * the ``maxexectime``/``maxwaittime`` stateless failsafe scanner (§3.4)
  * workflow DAGs with ``wait_for_parents`` + dynamic children (§3.4.2)
  * zero-trust authorization of every envelope (§3.4.6)

Concurrency model (this file plus database.py):

* Assignment, close, and failsafe mutations for one colony serialize on
  that colony's ``db.colony_lock`` — colonies never contend with each
  other, and a stale executor's close can no longer interleave with a
  failsafe reset (the close re-validates state + ownership under the
  lock before mutating).
* Long-polling executors park on a per-(colony, executortype) condition
  variable and are woken only when *their* queue gains work (submit,
  child release, failsafe requeue), instead of polling a global CV.
  A monotonically bumped version per queue closes the classic
  check-then-wait race without holding any lock across the DB probe.

Cron, generators and CFS are separate modules wired in by this server.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Callable

from ..analysis import authtrack
from ..analysis.authtrack import requires_auth
from ..analysis.contracts import no_locks_held
from ..analysis.locktrack import make_lock
from ..runtime import faults
from . import blobstore, idempotency, tracing
from .database import Database, MemoryDatabase
from .errors import (
    AuthError,
    ColoniesError,
    ConflictError,
    NotFoundError,
    NotLeaderError,
    TimeoutError_,
    ValidationError,
)
from .process import (
    FAILED,
    RUNNING,
    STATES,
    SUCCESSFUL,
    WAITING,
    Colony,
    Executor,
    Process,
    new_id,
    now_ns,
)
from .security import open_envelope
from .spec import FunctionSpec, WorkflowSpec

# The seed's kv bucket for colony users; survives only as the sqlite
# migration source (users are a first-class indexed table now).
USERS_TABLE = "users"


class _QueueSignal:
    """Wakeup channel for one (colony, executortype) ready queue."""

    __slots__ = ("cv", "version")

    def __init__(self, key: tuple[str, str] = ("", "")) -> None:
        self.cv = threading.Condition(make_lock(f"queuecv:{key[0]}:{key[1]}"))
        self.version = 0


class ColoniesServer:
    """A single Colonies server replica.

    ``serverid`` is the identity of the server owner (SHA3 of their public
    key); only that identity may create colonies. In HA mode, ``is_leader``
    and ``propose_assign`` are overridden by the cluster layer.
    """

    # HA replicas re-check leadership at this cadence while parked in
    # ``assign``; standalone servers sleep the full long-poll timeout and
    # rely purely on queue notifications.
    HA_LEADER_RECHECK_S = 0.5

    def __init__(
        self,
        serverid: str,
        db: Database | None = None,
        verify_signatures: bool = True,
        name: str = "colonies-0",
    ) -> None:
        self.name = name
        self.serverid = serverid
        self.db = db if db is not None else MemoryDatabase()
        self.verify_signatures = verify_signatures
        # Per-(colony, executortype) wakeup channels for long-poll assign.
        self._signals: dict[tuple[str, str], _QueueSignal] = {}
        self._signals_guard = make_lock("signals")
        # Leader-local per-colony assign serialization for the HA path (the
        # shared db.colony_lock cannot be held across a Raft proposal: the
        # commit is applied on another thread that needs that same lock).
        self._local_assign_locks: dict[str, threading.RLock] = {}
        self._handlers: dict[str, Callable[[str, dict], Any]] = {
            "addcolony": self._h_add_colony,
            "addexecutor": self._h_add_executor,
            "approveexecutor": self._h_approve_executor,
            "rejectexecutor": self._h_reject_executor,
            "removeexecutor": self._h_remove_executor,
            "listexecutors": self._h_list_executors,
            "adduser": self._h_add_user,
            "listusers": self._h_list_users,
            "addfunction": self._h_add_function,
            "listfunctions": self._h_list_functions,
            "submitfunctionspec": self._h_submit,
            "submitworkflow": self._h_submit_workflow,
            "assign": self._h_assign,
            "close": self._h_close,
            "addchild": self._h_add_child,
            "getprocess": self._h_get_process,
            "getprocesses": self._h_get_processes,
            "colonystats": self._h_stats,
        }
        # Extension points (cron/generator/fs register their handlers here).
        self.extensions: list[Any] = []
        # HA hooks — standalone servers are always leader. ``_propose_op``
        # serializes replicated ops (assign, close) through the Raft log;
        # every proposed entry carries a leader-stamped ``ts`` and a
        # stable ``opid`` so the apply is deterministic and replay-safe
        # (see REPLICATION.md, repro.analysis.replint).
        self._ha = False
        self._is_leader: Callable[[], bool] = lambda: True
        self._propose_op: Callable[[dict], None] | None = None
        self._stop = threading.Event()
        self._failsafe_thread: threading.Thread | None = None
        # Exceptions swallowed (but counted) by the failsafe loop; the
        # first one is logged with traceback. Surfaced via colonystats.
        self.failsafe_errors = 0

    # ------------------------------------------------------------------ RPC
    def handle(self, envelope: dict, external: bool = False) -> dict:
        """Entry point for all transports. Returns {"error":...} or {"result":...}.

        ``external=True`` (set by network transports) forces signature
        verification regardless of ``verify_signatures``: the unverified
        path exists only for in-process benchmark/test harnesses, never
        for envelopes that crossed a trust boundary (paper §3.4.6).
        """
        with tracing.span("colony.server.handle", payloadtype=envelope.get("payloadtype", "")):
            return self._dispatch(envelope, external)

    def _dispatch(self, envelope: dict, external: bool) -> dict:
        try:
            verify = self.verify_signatures or external
            identity, ptype, payload = open_envelope(
                envelope, verify=verify, allow_unverified=not verify
            )
            # Injected server death before dispatch: the request has no
            # effect and the transport sees a dead connection. The raise
            # (FaultInjected is a ConnectionError, not a ColoniesError)
            # deliberately escapes the handlers below.
            faults.hit("server.pre_dispatch", payloadtype=ptype)
            handler = self._handlers.get(ptype)
            if handler is None:
                for ext in self.extensions:
                    handler = ext.handlers().get(ptype)
                    if handler is not None:
                        break
            if handler is None:
                raise ValidationError(f"unknown payloadtype {ptype!r}")
            # Exactly-once mutating RPCs (ROBUSTNESS.md): a keyed envelope
            # whose (identity, msgid) already has a recorded reply is a
            # client retry of a committed operation — replay the reply
            # without re-running the handler. The msgid is covered by the
            # envelope signature, so only the original signer can replay.
            msgid = str(envelope.get("msgid") or "")
            dedup_key = ""
            if msgid and idempotency.classify(ptype) == idempotency.KEYED:
                dedup_key = f"{identity}:{msgid}"
                cached = self.db.dedup_get(dedup_key)
                if cached is not None:
                    return {"result": cached, "replayed": True}
            # Under REPRO_AUTH_CHECK=1 the scope arms the database guards:
            # colony-scoped access inside this dispatch requires a recorded
            # auth fact (see repro/analysis/authtrack.py).
            token = idempotency.set_current(msgid)
            try:
                with authtrack.request_scope():
                    result = handler(identity, payload)
            finally:
                idempotency.reset_current(token)
            # Record successes only: an error reply implies nothing
            # committed (handlers raise before mutating), so the retry
            # must re-execute, not replay the failure.
            if dedup_key:
                self.db.dedup_put(
                    dedup_key,
                    idempotency.reply_colony(ptype, payload, result),
                    now_ns(),
                    result,
                )
            # The crash-after-commit-before-reply window: effect and dedup
            # record are durable, the reply is lost.
            faults.hit("server.post_commit", payloadtype=ptype)
            return {"result": result}
        except NotLeaderError as e:
            return {"error": str(e), "status": e.status, "leader": e.leader}
        except ColoniesError as e:
            return {"error": str(e), "status": e.status}

    # ------------------------------------------------------------ auth utils
    # Each check records its verified (identity, colony, role) as an auth
    # fact for the current request (a no-op unless REPRO_AUTH_CHECK=1);
    # colony-scoped database access without a matching fact then raises.
    def _require_server_owner(self, identity: str) -> None:
        if identity != self.serverid:
            raise AuthError("requires server owner")
        authtrack.record(identity, authtrack.ANY_COLONY, "server")

    def _require_colony_owner(self, identity: str, colonyname: str) -> Colony:
        colony = self.db.get_colony(colonyname)
        if identity != colony.colonyid:
            raise AuthError("requires colony owner")
        authtrack.record(identity, colonyname, "owner")
        return colony

    def _require_member(self, identity: str, colonyname: str) -> Executor | None:
        """Approved executor OR registered user OR colony owner."""
        colony = self.db.get_colony(colonyname)
        if identity == colony.colonyid:
            authtrack.record(identity, colonyname, "owner")
            return None
        try:
            ex = self.db.get_executor(identity)
            if ex.colonyname == colonyname and ex.state == "approved":
                authtrack.record(identity, colonyname, "executor")
                self.db.touch_executor(identity, now_ns())
                return ex
        except NotFoundError:
            pass
        user = self.db.user_get(identity)
        if user is not None and user.get("colonyname") == colonyname:
            authtrack.record(identity, colonyname, "member")
            return None
        raise AuthError("identity is not a member of the colony")

    def _require_executor(self, identity: str, colonyname: str) -> Executor:
        try:
            ex = self.db.get_executor(identity)
        except NotFoundError as e:
            raise AuthError("unknown executor identity") from e
        if ex.colonyname != colonyname:
            raise AuthError("executor belongs to another colony")
        if ex.state != "approved":
            raise AuthError(f"executor not approved (state={ex.state})")
        authtrack.record(identity, colonyname, "executor")
        self.db.touch_executor(identity, now_ns())
        return ex

    # -------------------------------------------------------------- handlers
    def _h_add_colony(self, identity: str, payload: dict) -> dict:
        self._require_server_owner(identity)
        colony = Colony.from_dict(payload.get("colony", payload))
        if not colony.colonyname or not colony.colonyid:
            raise ValidationError("colony needs colonyname and colonyid")
        self.db.add_colony(colony)
        return colony.to_dict()

    def _h_add_executor(self, identity: str, payload: dict) -> dict:
        ex = Executor.from_dict(payload.get("executor", payload))
        self._require_colony_owner(identity, ex.colonyname)
        if not ex.executorid or not ex.executortype:
            raise ValidationError("executor needs executorid and executortype")
        ex.state = "pending"
        ex.commissiontime_ns = now_ns()
        self.db.add_executor(ex)
        return ex.to_dict()

    def _h_approve_executor(self, identity: str, payload: dict) -> dict:
        ex = self.db.get_executor(payload["executorid"])
        self._require_colony_owner(identity, ex.colonyname)
        self.db.set_executor_state(ex.executorid, "approved")
        return {"executorid": ex.executorid, "state": "approved"}

    def _h_reject_executor(self, identity: str, payload: dict) -> dict:
        ex = self.db.get_executor(payload["executorid"])
        self._require_colony_owner(identity, ex.colonyname)
        self.db.set_executor_state(ex.executorid, "rejected")
        return {"executorid": ex.executorid, "state": "rejected"}

    def _h_remove_executor(self, identity: str, payload: dict) -> dict:
        ex = self.db.get_executor(payload["executorid"])
        self._require_colony_owner(identity, ex.colonyname)
        self.db.remove_executor(ex.executorid)
        return {"executorid": ex.executorid, "removed": True}

    def _h_list_executors(self, identity: str, payload: dict) -> list[dict]:
        colony = payload["colonyname"]
        self._require_member(identity, colony)
        return [e.to_dict() for e in self.db.list_executors(colony)]

    def _h_add_user(self, identity: str, payload: dict) -> dict:
        colony = payload["colonyname"]
        self._require_colony_owner(identity, colony)
        user = {
            "userid": payload["userid"],
            "username": payload.get("username", ""),
            "colonyname": colony,
        }
        self.db.user_put(user)
        return user

    def _h_list_users(self, identity: str, payload: dict) -> list[dict]:
        colony = payload["colonyname"]
        self._require_member(identity, colony)
        return self.db.user_list(colony)

    def _h_add_function(self, identity: str, payload: dict) -> dict:
        colony = payload["colonyname"]
        ex = self._require_executor(identity, colony)
        self.db.add_function(ex.executorid, colony, payload["funcname"])
        return {"executorid": ex.executorid, "funcname": payload["funcname"]}

    def _h_list_functions(self, identity: str, payload: dict) -> list[dict]:
        colony = payload["colonyname"]
        self._require_member(identity, colony)
        return self.db.list_functions(colony, payload.get("executorid"))

    # -- submit -------------------------------------------------------------
    def _h_submit(self, identity: str, payload: dict) -> dict:
        spec = FunctionSpec.from_dict(payload.get("spec", payload))
        if not spec.conditions.colonyname:
            raise ValidationError("spec.conditions.colonyname required")
        if not spec.conditions.executortype:
            raise ValidationError("spec.conditions.executortype required")
        self._require_member(identity, spec.conditions.colonyname)
        p = Process.create(spec)
        self.db.add_process(p)
        self._notify_queue([self._queue_key(p)])
        return p.to_dict()

    def _h_submit_workflow(self, identity: str, payload: dict) -> dict:
        wf = WorkflowSpec.from_dict(payload.get("workflow", payload))
        colony = wf.colonyname or (
            wf.specs[0].conditions.colonyname if wf.specs else ""
        )
        if not colony:
            raise ValidationError("workflow needs a colonyname")
        self._require_member(identity, colony)
        if not wf.specs:
            raise ValidationError("empty workflow")
        for s in wf.specs:
            s.conditions.colonyname = s.conditions.colonyname or colony
        wf.validate()
        procs = self.submit_workflow_processes(wf)
        self._notify_queue(
            [self._queue_key(p) for p in procs if not p.wait_for_parents]
        )
        return {
            "workflowid": procs[0].workflowid,
            "processes": [p.to_dict() for p in procs],
        }

    @requires_auth("member")
    def submit_workflow_processes(self, wf: WorkflowSpec) -> list[Process]:
        """DAG expansion (paper §3.4.2): one process per node, linked by ids."""
        from .workflow import expand_workflow

        procs = expand_workflow(wf)
        for p in procs:
            self.db.add_process(p)
        return procs

    # -- assign ---------------------------------------------------------------
    def _h_assign(self, identity: str, payload: dict) -> dict:
        colony = payload["colonyname"]
        timeout = float(payload.get("timeout", 10.0))
        ex = self._require_executor(identity, colony)
        p = self.assign(colony, ex, timeout)
        if p is None:
            raise TimeoutError_("no process assigned within timeout")
        return p.to_dict()

    @no_locks_held()
    def assign(self, colony: str, ex: Executor, timeout: float) -> Process | None:
        """Long-poll assignment (paper §3.3: the server *hangs* the request).

        Event-driven: the request parks on the (colony, executortype)
        signal and is woken exactly when that queue gains work.
        """
        deadline = now_ns() + int(timeout * 1e9)
        sig = self._signal((colony, ex.executortype))
        while not self._stop.is_set():
            if not self._is_leader():
                raise NotLeaderError("assign must be served by the leader")
            with sig.cv:
                version = sig.version
            p = self._try_assign_once(colony, ex)
            if p is not None:
                return p
            remaining = (deadline - now_ns()) / 1e9
            if remaining <= 0:
                return None
            # HA replicas wake periodically to notice lost leadership;
            # standalone servers sleep until notified (or timeout).
            tick = self.HA_LEADER_RECHECK_S if self._ha else remaining
            with tracing.span("colony.broker.assign_wait"), sig.cv:
                if sig.version == version:  # nothing arrived since we probed
                    sig.cv.wait(timeout=min(remaining, tick))
        return None

    def _local_assign_lock(self, colony: str) -> threading.RLock:
        with self._signals_guard:
            lk = self._local_assign_locks.get(colony)
            if lk is None:
                lk = self._local_assign_locks[colony] = make_lock(
                    f"assignlocal:{colony}"
                )
            return lk

    def _try_assign_once(self, colony: str, ex: Executor) -> Process | None:
        if self._propose_op is not None:
            # HA: leader-local serialization; Raft log order plus the
            # WAITING CAS in apply_assign make assignment exactly-once.
            lock = self._local_assign_lock(colony)
        else:
            lock = self.db.colony_lock(colony)
        with lock:
            cands = self.db.candidates(colony, ex.executortype, ex.executorname)
            for p in cands:
                # Leader-stamped entry: the wall clock and the op identity
                # are fixed HERE, before the Raft log, so the apply cone
                # stays deterministic (replint REP001/REP004).
                op = {
                    "op": "assign",
                    "opid": new_id(),
                    "processid": p.processid,
                    "executorid": ex.executorid,
                    "ts": now_ns(),
                    # The request's idempotency key rides the Raft entry so
                    # followers see which client RPC produced this op
                    # (ROBUSTNESS.md; "" for unkeyed/internal callers).
                    "msgid": idempotency.current(),
                }
                if self._propose_op is not None:
                    # HA path: serialize through the Raft log before applying.
                    # The apply's WAITING CAS may lose (failsafe expiry,
                    # leader churn) and the cluster swallows that conflict —
                    # so confirm this op actually won before handing the
                    # process to the executor.
                    self._propose_op(op)
                    assigned = self.db.get_process(p.processid)
                    if (
                        assigned.state != RUNNING
                        or assigned.assignedexecutorid != ex.executorid
                    ):
                        continue  # lost the race — try the next candidate
                    return assigned
                self.apply_assign(op)
                return self.db.get_process(p.processid)
        return None

    @requires_auth("executor")
    def apply_assign(self, op: dict) -> None:
        """State-machine apply for an assign op (also invoked by Raft commit).

        Compare-and-swap on ``state == WAITING`` — idempotent under Raft
        replay, and safe against a failsafe reset racing the assignment.
        """
        p = self.db.get_process(op["processid"])
        with self.db.colony_lock(p.colonyname):
            p = self.db.get_process(op["processid"])  # re-read under the lock
            if p.state != WAITING:
                raise ConflictError("process no longer waiting")
            ts = op["ts"]
            p.state = RUNNING
            p.isassigned = True
            p.assignedexecutorid = op["executorid"]
            p.starttime_ns = ts
            if p.spec.maxexectime and p.spec.maxexectime > 0:
                p.deadline_ns = ts + p.spec.maxexectime * 10**9
            else:
                p.deadline_ns = 0
            # Dataflow (Table 4): inputs = concatenated parent outputs.
            if p.parents:
                inputs: list[Any] = []
                for parent_id in p.parents:
                    parent = self.db.get_process(parent_id)
                    inputs.extend(parent.output)
                p.inputs = inputs
            self.db.update_process(p)

    # -- close ---------------------------------------------------------------
    def _h_close(self, identity: str, payload: dict) -> dict:
        pid = payload["processid"]
        p = self.db.get_process(pid)
        ex = self._require_executor(identity, p.colonyname)
        if p.assignedexecutorid != ex.executorid or p.state != RUNNING:
            # e.g. the failsafe already reset this process (paper §4.1:
            # "The previous executor then receives an error").
            raise ConflictError("process is not assigned to this executor")
        # Leader-stamped entry (REP001/REP004): the end timestamp is fixed
        # before the Raft log so close replays identically on every replica.
        op = {
            "op": "close",
            "opid": new_id(),
            "processid": pid,
            "executorid": ex.executorid,
            "successful": bool(payload.get("successful", True)),
            "out": payload.get("out", []),
            "errors": payload.get("errors", []),
            "ts": now_ns(),
            # Idempotency key of the originating RPC, replicated so
            # followers can attribute the close (ROBUSTNESS.md).
            "msgid": idempotency.current(),
        }
        if self._propose_op is not None:
            # HA path: serialize close through the Raft log. The apply's
            # RUNNING + owner CAS may lose (failsafe reset interleaving)
            # and the cluster swallows that conflict — confirm this close
            # actually won by checking the leader-stamped end time landed.
            self._propose_op(op)
            closed = self.db.get_process(pid)
            if (
                closed.state not in (SUCCESSFUL, FAILED)
                or closed.endtime_ns != op["ts"]
            ):
                raise ConflictError("process is not assigned to this executor")
        else:
            self.apply_close(op)
        return self.db.get_process(pid).to_dict()

    @requires_auth("executor")
    def apply_close(self, op: dict) -> None:
        """State-machine apply for a close op (also invoked by Raft commit).

        Deterministic by construction: the wall clock arrives leader-stamped
        as ``op["ts"]`` and the RUNNING + owner CAS inside ``close_process``
        turns a Raft replay into a clean ConflictError instead of a double
        mutation.
        """
        p = self.db.get_process(op["processid"])
        self.close_process(
            p,
            bool(op.get("successful", True)),
            op.get("out", []),
            op.get("errors", []),
            op["executorid"],
            ts=op["ts"],
        )

    @requires_auth("executor")
    def close_process(
        self,
        p: Process,
        succeeded: bool,
        output: list[Any],
        errors: list[str],
        expected_executorid: str | None = None,
        *,
        ts: int,
    ) -> None:
        """Close + stateless DAG propagation (paper §3.4.2).

        Serialized against assign/failsafe on the colony lock: the process
        is re-read and CAS-checked (still RUNNING, still owned by
        ``expected_executorid``) before any mutation, so a failsafe reset
        that interleaved after the caller's precheck turns this into a
        clean ConflictError instead of silently overwriting a re-queued
        or re-assigned process.

        ``ts`` is the leader-stamped end time from the replicated close
        entry — reading the wall clock inside this mutation would make
        the apply nondeterministic across replicas (replint REP001), so
        it is required, never defaulted.
        """
        released: list[tuple[str, str]] = []
        with self.db.colony_lock(p.colonyname):
            fresh = self.db.get_process(p.processid)
            if fresh.state != RUNNING:
                raise ConflictError("process is not running")
            if (
                expected_executorid is not None
                and fresh.assignedexecutorid != expected_executorid
            ):
                raise ConflictError("process is not assigned to this executor")
            fresh.state = SUCCESSFUL if succeeded else FAILED
            fresh.endtime_ns = ts
            fresh.output = list(output)
            fresh.errors = list(errors)
            fresh.deadline_ns = 0
            self.db.update_process(fresh)
            if succeeded:
                for child_id in fresh.children:
                    child = self._maybe_release_child(child_id)
                    if child is not None:
                        released.append(self._queue_key(child))
            else:
                # Fail descendants so workflows terminate instead of hanging.
                self._fail_descendants(
                    fresh, f"parent process {fresh.processid} failed", ts
                )
        if released:
            self._notify_queue(released)

    def _maybe_release_child(self, child_id: str) -> Process | None:
        child = self.db.get_process(child_id)
        if not child.wait_for_parents:
            return None
        for parent_id in child.parents:
            if self.db.get_process(parent_id).state != SUCCESSFUL:
                return None
        child.wait_for_parents = False
        self.db.update_process(child)
        if hasattr(self.db, "requeue"):
            self.db.requeue(child)
        return child

    def _fail_descendants(self, p: Process, reason: str, ts: int) -> None:
        # ``ts`` is the leader-stamped (or failsafe-scan) timestamp of the
        # triggering mutation — descendants inherit it so the whole cascade
        # is deterministic under Raft replay (replint REP001).
        for child_id in p.children:
            child = self.db.get_process(child_id)
            if child.state in (WAITING, RUNNING):
                child.state = FAILED
                child.endtime_ns = ts
                child.errors = [reason]
                self.db.update_process(child)
                self._fail_descendants(child, reason, ts)

    # -- dynamic children (MapReduce on the fly, paper §3.4.2) ----------------
    def _h_add_child(self, identity: str, payload: dict) -> dict:
        parent_id = payload["processid"]
        parent = self.db.get_process(parent_id)
        ex = self._require_executor(identity, parent.colonyname)
        if parent.assignedexecutorid != ex.executorid or parent.state != RUNNING:
            raise AuthError("only the assigned executor may extend the DAG")
        spec = FunctionSpec.from_dict(payload["spec"])
        spec.conditions.colonyname = parent.colonyname
        child = Process.create(spec)
        insert_after_parent = bool(payload.get("waitforparent", False))
        # Serialized against close/failsafe on the colony lock, with a
        # CAS-revalidation like close_process: without it, a close (or
        # failsafe reset) interleaving between the precheck above and the
        # children append below would either lose the child edge entirely
        # or strand a waitforparent child whose parent already succeeded.
        with self.db.colony_lock(parent.colonyname):
            parent = self.db.get_process(parent_id)  # re-read under the lock
            if parent.assignedexecutorid != ex.executorid or parent.state != RUNNING:
                raise ConflictError("parent closed or reassigned while extending the DAG")
            child.workflowid = parent.workflowid
            if insert_after_parent:
                child.parents = [parent_id]
                child.wait_for_parents = True
            self.db.add_process(child)
            parent.children = parent.children + [child.processid]
            self.db.update_process(parent)
        if not child.wait_for_parents:
            self._notify_queue([self._queue_key(child)])
        return child.to_dict()

    # -- introspection ---------------------------------------------------------
    def _h_get_process(self, identity: str, payload: dict) -> dict:
        p = self.db.get_process(payload["processid"])
        self._require_member(identity, p.colonyname)
        return p.to_dict()

    def _h_get_processes(self, identity: str, payload: dict) -> list[dict]:
        colony = payload["colonyname"]
        self._require_member(identity, colony)
        return [
            p.to_dict()
            for p in self.db.list_processes(
                colony, payload.get("state"), int(payload.get("count", 100))
            )
        ]

    def _h_stats(self, identity: str, payload: dict) -> dict:
        colony = payload["colonyname"]
        self._require_member(identity, colony)
        # O(1) counter read — total over every state ever observed, so a
        # process in an unexpected state can never KeyError the endpoint.
        stats: dict[str, int] = {s: 0 for s in STATES}
        for state, n in self.db.colony_stats(colony).items():
            stats[state] = stats.get(state, 0) + n
        stats["executors"] = len(self.db.list_executors(colony))
        stats["failsafe_errors"] = self.failsafe_errors
        # Blob-plane health (STORAGE.md): per-shard op/byte/repair
        # counters aggregated over every live ShardedStorage in the
        # process (broker + executors share one process in this repro,
        # exactly like the InProc transport).
        stats["blob"] = blobstore.aggregate_stats()
        # The process's own counters (signatures, generator fires, the
        # engine's batches and compiles): see PERF.md, "Tracing".
        stats["trace"] = tracing.counters()
        return stats

    # -- failsafe (paper §3.4) --------------------------------------------------
    @no_locks_held()
    def failsafe_scan(self) -> dict:
        """One failsafe pass; returns counters (also used by tests).

        The deadline indexes hand back only expired processes, and each
        mutation re-validates under the colony lock so a concurrent close
        (or another replica's scan) can't be clobbered.
        """
        ts = now_ns()
        reset = failed = expired = 0
        woken: list[tuple[str, str]] = []
        for p in self.db.running_past_deadline(ts):
            with self.db.colony_lock(p.colonyname):
                try:
                    cur = self.db.get_process(p.processid)
                except NotFoundError:
                    continue
                if (
                    cur.state != RUNNING
                    or not cur.deadline_ns
                    or cur.deadline_ns >= ts
                ):
                    continue  # closed or re-assigned since the index read
                if cur.retries + 1 > max(cur.spec.maxretries, 0):
                    cur.state = FAILED
                    cur.endtime_ns = ts
                    cur.errors = cur.errors + [
                        "maxretries exceeded after maxexectime reset"
                    ]
                    self.db.update_process(cur)
                    self._fail_descendants(
                        cur, f"parent process {cur.processid} failed", ts
                    )
                    failed += 1
                else:
                    # Reset back to the queue — another executor picks it up.
                    cur.state = WAITING
                    cur.isassigned = False
                    cur.assignedexecutorid = ""
                    cur.starttime_ns = 0
                    cur.deadline_ns = 0
                    cur.retries += 1
                    self.db.update_process(cur)
                    if hasattr(self.db, "requeue"):
                        self.db.requeue(cur)
                    woken.append(self._queue_key(cur))
                    reset += 1
        for p in self.db.waiting_past_deadline(ts):
            with self.db.colony_lock(p.colonyname):
                try:
                    cur = self.db.get_process(p.processid)
                except NotFoundError:
                    continue
                if (
                    cur.state != WAITING
                    or not cur.waitdeadline_ns
                    or cur.waitdeadline_ns >= ts
                ):
                    continue
                cur.state = FAILED
                cur.endtime_ns = ts
                cur.errors = cur.errors + ["maxwaittime exceeded"]
                self.db.update_process(cur)
                self._fail_descendants(
                    cur, f"parent process {cur.processid} failed", ts
                )
                expired += 1
        if woken:
            self._notify_queue(woken)
        return {"reset": reset, "failed": failed, "waitexpired": expired}

    def start_background(self, failsafe_interval: float = 0.25) -> None:
        """Start the periodic failsafe scanner (leader-gated in HA mode).

        The loop must survive anything a scan or extension tick throws —
        a dead failsafe thread silently disables the paper's §3.4 story.
        Failures are counted (``failsafe_errors``, surfaced via
        ``colonystats``) and the first traceback is logged once."""

        def loop() -> None:
            while not self._stop.wait(failsafe_interval):
                try:
                    if self._is_leader():
                        self.failsafe_scan()
                    for ext in self.extensions:
                        tick = getattr(ext, "tick", None)
                        if tick is not None and self._is_leader():
                            tick()
                except Exception:
                    if self.failsafe_errors == 0:
                        logging.getLogger(__name__).exception(
                            "failsafe loop error on %s (counting further "
                            "errors silently; see colonystats.failsafe_errors)",
                            self.name,
                        )
                    self.failsafe_errors += 1

        self._failsafe_thread = threading.Thread(target=loop, daemon=True)
        self._failsafe_thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._notify_queue()
        if self._failsafe_thread is not None:
            self._failsafe_thread.join(timeout=2)

    # -- queue wakeups -------------------------------------------------------
    @staticmethod
    def _queue_key(p: Process) -> tuple[str, str]:
        return (p.colonyname, p.spec.conditions.executortype)

    def _signal(self, key: tuple[str, str]) -> _QueueSignal:
        with self._signals_guard:
            sig = self._signals.get(key)
            if sig is None:
                sig = self._signals[key] = _QueueSignal(key)
            return sig

    def _notify_queue(self, keys: list[tuple[str, str]] | None = None) -> None:
        """Wake long-poll waiters. ``keys=None`` (extensions, stop) wakes all."""
        if keys is None:
            with self._signals_guard:
                sigs = list(self._signals.values())
        else:
            sigs = [self._signal(k) for k in set(keys)]
        for sig in sigs:
            with sig.cv:
                sig.version += 1
                sig.cv.notify_all()

    # -- HA wiring ----------------------------------------------------------------
    def set_leader_check(self, fn: Callable[[], bool]) -> None:
        self._ha = True
        self._is_leader = fn

    def set_op_proposer(self, fn: Callable[[dict], None]) -> None:
        """Route replicated ops (assign, close, …) through the Raft log.

        The callable must block until the entry is committed and applied
        locally (``ThreadedRaftCluster.propose_and_wait`` semantics).
        """
        self._propose_op = fn

    # Back-compat: PR 1 named the hook after its only op at the time.
    set_assign_proposer = set_op_proposer
