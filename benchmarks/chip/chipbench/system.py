"""The system under test, built from the program's own parts.

This is the one module of the harness that knows the program's
interfaces: its architecture registry, the executor it serves with, the
layout of its parameters and the colony around it. It starts what
``launch/serve.py``'s ``serve()`` starts (a standalone server with its
failsafe ticking, a ``ServeExecutor``, a generator that batches packs)
and serves the colony over the HTTP transport as well, so that the load
generator reaches it from another process as a remote user would.
"""

from __future__ import annotations

import contextlib
import threading
import types

import jax

from .weights import Dims

COLONY = "bench"
EXECUTOR_TYPE = "tpu-serve"
BATCH_MAXEXEC_S = 300


def register(config: dict) -> str:
    """Make the configuration's architecture known to the program's
    registry and return its id. A cut of a registered architecture
    (``program.base_arch`` with ``program.overrides``) is registered
    under its own id; the executor then builds it through its normal path."""
    from repro.configs import ARCHS, get_config

    prog = config["program"]
    arch = prog["arch"]
    if "base_arch" in prog and arch not in ARCHS:
        base, overrides = prog["base_arch"], dict(prog.get("overrides", {}))
        ARCHS[arch] = types.SimpleNamespace(
            ARCH_ID=arch,
            full=lambda: get_config(base, "full").copy(**overrides),
            smoke=lambda: get_config(base, "smoke"))
    return arch


def model_config(config: dict, variant: str):
    """The ModelConfig the executor will build for this configuration."""
    from repro.runtime.jax_executor import executor_config

    return executor_config(register(config), variant)


def dims_of(mc) -> Dims:
    if mc.family != "dense" or mc.attention != "gqa" or mc.activation != "swiglu":
        raise ValueError(f"{mc.name}: the harness serves dense GQA SwiGLU models only")
    if mc.qkv_bias or not mc.use_rope or mc.sliding_window:
        raise ValueError(f"{mc.name}: qkv bias, no rotary or a window is not modelled")
    return Dims(layers=mc.num_layers, d_model=mc.d_model, heads=mc.num_heads,
                kv_heads=mc.num_kv_heads, head_dim=mc.head_dim, d_ff=mc.d_ff,
                vocab=mc.vocab_size, tied=mc.tied_embeddings, norm=mc.norm,
                norm_eps=mc.norm_eps, rope_theta=mc.rope_theta)


def program_params(w: dict, dims: Dims, mc) -> dict:
    """The benchmark's weights in the program's parameter tree (the same
    buffers, no copy), checked leaf by leaf against the tree the program
    would build for itself."""
    from repro.models import model_spec
    from repro.models.sharding import abstract_params

    def norm(prefix: str) -> dict:
        out = {"scale": w[f"{prefix}.scale"]}
        if dims.norm == "layernorm":
            out["bias"] = w[f"{prefix}.bias"]
        return out

    block = {
        "norm1": norm("layers.norm1"), "norm2": norm("layers.norm2"),
        "mixer": {k: w[f"layers.{k}"] for k in ("wq", "wk", "wv", "wo")},
        "mlp": {"w_gate": w["layers.w_gate"], "w_in": w["layers.w_up"],
                "w_out": w["layers.w_down"]},
    }
    params = {"embed": w["embed"], "groups": {"b0": block}, "norm_f": norm("norm_f")}
    if not dims.tied:
        params["lm_head"] = w["head"]
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
