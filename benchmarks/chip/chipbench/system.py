"""The system under test, built from the program's own parts.

This is the one module of the harness that knows the program's
interfaces: its architecture registry, the executor it serves with, the
check of a parameter tree against the program's own, and the colony
around it (a block module maps its weights onto that tree). It starts what
``launch/serve.py``'s ``serve()`` starts (a standalone server with its
failsafe ticking, a ``ServeExecutor``, a generator that batches packs)
and serves the colony over the HTTP transport as well, so that the load
generator reaches it from another process as a remote user would.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import types

import jax

COLONY = "bench"
EXECUTOR_TYPE = "tpu-serve"
BATCH_MAXEXEC_S = 300


def register(config: dict) -> str:
    """Make the configuration's architecture known to the program's
    registry and return its id. A cut of a registered architecture
    (``program.base_arch`` with ``program.overrides``) is registered under
    its own id, and its ``ModelConfig`` is named by that id: the executor,
    built from the id, builds the cut. An override that holds a dict goes
    into the nested config of that name field by field, so that
    ``{"moe": {"num_experts": 8}}`` keeps the rest of the base's ``moe``.
    The smoke variant is the base's smoke model under the cut's id."""
    from repro.configs import ARCHS, get_config

    prog = config["program"]
    arch = prog["arch"]
    if "base_arch" in prog and arch not in ARCHS:
        base, overrides = prog["base_arch"], dict(prog.get("overrides", {}))
        ARCHS[arch] = types.SimpleNamespace(
            ARCH_ID=arch,
            full=lambda: _override(get_config(base, "full"), dict(overrides, name=arch)),
            smoke=lambda: get_config(base, "smoke").copy(name=arch))
    return arch


def _override(obj, overrides: dict):
    """``obj`` (a dataclass) with ``overrides`` applied, into nested
    dataclasses where an override is a dict."""
    fields = {f.name for f in dataclasses.fields(obj)}
    kw = {}
    for key, value in overrides.items():
        if key not in fields:
            raise KeyError(f"{type(obj).__name__} has no field {key!r}")
        inner = getattr(obj, key)
        if isinstance(value, dict) and dataclasses.is_dataclass(inner):
            value = _override(inner, value)
        kw[key] = value
    return dataclasses.replace(obj, **kw)


def model_config(config: dict, variant: str):
    """The ModelConfig the executor will build for this configuration."""
    from repro.runtime.jax_executor import executor_config

    return executor_config(register(config), variant)


def checked(params: dict, mc) -> dict:
    """``params``, once checked leaf by leaf (structure, shapes, dtypes)
    against the tree the program would build for ``mc`` itself."""
    from repro.models import model_spec
    from repro.models.sharding import abstract_params

    want = abstract_params(model_spec(mc), jax.numpy.dtype(mc.param_dtype))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree.structure(got) != jax.tree.structure(want) or jax.tree.leaves(got) != \
            jax.tree.leaves(want):
        raise ValueError(f"weights do not match the program's parameter tree:\n{got}\n{want}")
    return params


@contextlib.contextmanager
def executor_weights(params: dict):
    """Let ``ServeExecutor`` take ``params`` where it would initialise its
    own (it has no argument for them): its module's ``_init_params`` is
    swapped for the time of the constructor."""
    from repro.runtime import jax_executor

    original = jax_executor._init_params
    jax_executor._init_params = lambda cfg, seed: params
    try:
        yield
    finally:
        jax_executor._init_params = original


class Colony:
    """Server, HTTP front, colony, generator and (later) the executor."""

    def __init__(self, storage_dir: str, traffic: dict) -> None:
        from repro.core import Colonies, Crypto, InProcTransport
        from repro.core.cluster import standalone_server
        from repro.core.fs import LocalStorage
        from repro.core.http_transport import ColoniesHttpServer

        self.server_prv, self.colony_prv = Crypto.prvkey(), Crypto.prvkey()
        self.server = standalone_server(Crypto.id(self.server_prv))
        self.server.start_background(failsafe_interval=0.1)
        self.http = ColoniesHttpServer(self.server)
        self.http.start()
        self.client = Colonies(InProcTransport([self.server]))
        self.client.add_colony(COLONY, Crypto.id(self.colony_prv), self.server_prv)
        self.storage_dir = storage_dir
        self.storage = LocalStorage(storage_dir)
        wf = {"colonyname": COLONY, "functionspecs": [
            {"nodename": "batch", "funcname": "generate_batch",
             "conditions": {"executortype": EXECUTOR_TYPE, "dependencies": []},
             "maxexectime": BATCH_MAXEXEC_S}]}
        gen = traffic["generator"]
        self.generatorid = self.client.add_generator(
            {"colonyname": COLONY, "name": "batcher", "queuesize": int(gen["queuesize"]),
             "timeout": float(gen["timeout_s"]), "workflow": wf},
            self.colony_prv)["generatorid"]
        self.worker = None

    def start_executor(self, arch: str, variant: str, params: dict, max_len: int):
        """Build the ServeExecutor (registered, not yet polling)."""
        from repro.runtime.jax_executor import ServeExecutor

        with executor_weights(params):
            self.worker = ServeExecutor(
                self.client, COLONY, "serve-0", EXECUTOR_TYPE, self.storage,
                colony_prvkey=self.colony_prv, arch=arch, variant=variant,
                max_len=max_len)
        return self.worker

    def processes(self) -> list[dict]:
        """Every batch process the broker holds, as the broker records it."""
        return [p.to_dict() for p in self.server.db.list_processes(COLONY, count=1 << 30)]

    def stop(self) -> None:
        if self.worker is not None:
            self.worker.stop()
        self.http.stop()
        self.server.stop()


class FailureWatch:
    """Tells the load generator which requests' batches failed, so that
    it stops polling for them; they count as misses."""

    def __init__(self, colony: Colony, notify) -> None:
        self.colony, self.notify = colony, notify
        self._seen: set[str] = set()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(0.5):
            self.scan()

    def scan(self) -> None:
        for proc in self.colony.server.db.list_processes(COLONY, state="failed", count=1 << 30):
            if proc.processid in self._seen:
                continue
            self._seen.add(proc.processid)
            for arg in proc.spec.kwargs.get("packed_args", []):
                self.notify(arg["request_id"])

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)
        self.scan()
