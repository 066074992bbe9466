"""JAX executors — the meta-description → executable translation layer.

The paper's executors translate function specifications into Kubernetes
deployments or Slurm scripts; ours translate them into jitted JAX
programs. A ``train`` spec becomes a checkpointed training loop; an
``evaluate`` spec becomes an eval sweep from the latest CFS checkpoint;
``generate_batch`` (fired by the dynamic-batching generator) becomes one
batched inference call.

Fault tolerance is the broker's: each handler resumes from the latest
CFS checkpoint, so a ``maxexectime`` re-assignment after an executor
crash loses at most ``checkpoint_every`` steps.
"""

from __future__ import annotations

import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ModelConfig, TrainConfig, get_config
from ..core.client import Colonies
from ..core.errors import ValidationError
from ..core.executor import ExecutorBase, ProcessContext
from ..core.fs import CFSClient, Storage
from ..core.retry import RetryPolicy
from ..data.pipeline import SyntheticTokens
from ..train.checkpoint import CheckpointManager
from ..train.train_step import init_state, make_eval_step, make_train_step
from ..models import init_params, model_spec
from .chaos import SimulatedCrash

# Default blob-plane retry: generous enough to ride out one storage
# shard dying mid-operation (ShardedStorage already tolerates R−1 shard
# failures per call; this covers the window where ALL of a key's
# replicas are briefly unreachable).
BLOB_RETRY = RetryPolicy(base_s=0.01, cap_s=0.25, deadline_s=10.0, budget=6)


class JaxExecutorBase(ExecutorBase):
    """ExecutorBase + CFS access + crash simulation support.

    Implements the paper's fs sync directives (§3.4.5, Listing 2): before
    a handler runs, every ``fs.snapshots`` entry is materialized and
    every ``fs.dirs`` entry synced down into the process workdir; after
    it succeeds, ``fs.dirs`` entries with ``upload`` sync back up. All
    blob traffic is retry-backed (see BLOB_RETRY / CFSClient).
    """

    def __init__(self, client: Colonies, colonyname: str, executorname: str,
                 executortype: str, storage: Storage, colony_prvkey: str | None = None,
                 blob_retry: RetryPolicy | None = BLOB_RETRY, **kw: Any) -> None:
        super().__init__(client, colonyname, executorname, executortype,
                         colony_prvkey=colony_prvkey, **kw)
        self.storage = storage
        self.cfs = CFSClient(client, storage, self.prvkey, retry=blob_retry)

    def _execute(self, process) -> None:  # crash passthrough for chaos tests
        try:
            super()._execute(process)
        except SimulatedCrash:
            self.failed += 1  # vanish without closing — failsafe must recover

    # ------------------------------------------------- fs sync directives
    def _mount_dir(self, ctx: ProcessContext, directive_dir: str) -> str:
        """Resolve a directive's ``dir`` inside the process workdir.

        ``dir`` is relative to ``fs.mount`` (absolute paths are
        re-anchored by stripping the mount prefix); the result must stay
        inside the workdir — directives are part of the untrusted spec.
        """
        fs = ctx.process.spec.fs
        d = directive_dir or ""
        if fs.mount and d.startswith(fs.mount):
            d = d[len(fs.mount):]
        d = d.lstrip("/")
        base = ctx.workdir or "."
        for comp in d.split("/"):
            if comp in (".", "..") or "\\" in comp:
                raise ValidationError(f"unsafe fs directive dir {directive_dir!r}")
        dest = os.path.join(base, d) if d else base
        os.makedirs(dest, exist_ok=True)
        return dest

    def _sync_before(self, ctx: ProcessContext) -> None:
        fs = ctx.process.spec.fs
        for snap in fs.snapshots:
            self.cfs.materialize_snapshot(
                self.colonyname, snap.snapshotid, self._mount_dir(ctx, snap.dir)
            )
        for d in fs.dirs:
            self.cfs.sync_down(self.colonyname, d.label, self._mount_dir(ctx, d.dir))

    def _sync_after(self, ctx: ProcessContext) -> None:
        for d in ctx.process.spec.fs.dirs:
            if d.upload:
                self.cfs.sync_up(
                    self.colonyname, d.label, self._mount_dir(ctx, d.dir)
                )


def executor_config(arch: str, variant: str = "smoke", use_pallas: bool = False) -> ModelConfig:
    """The model an executor builds: ``smoke`` in float32 (CPU tests);
    every other variant in the config's own parameter and compute dtypes."""
    cfg = get_config(arch, variant)
    if variant == "smoke":
        cfg = cfg.copy(param_dtype="float32", compute_dtype="float32")
    return cfg.copy(use_pallas=use_pallas)


def _init_params(cfg: ModelConfig, seed: int):
    return init_params(jax.random.key(seed), model_spec(cfg), jnp.dtype(cfg.param_dtype))


class TrainerExecutor(JaxExecutorBase):
    """Handles ``train`` and ``evaluate`` function specs."""

    def __init__(self, *args: Any, die_at_step: int | None = None, **kw: Any) -> None:
        super().__init__(*args, **kw)
        self.die_at_step = die_at_step
        self.register_function("train", self.train)
        self.register_function("evaluate", self.evaluate)

    # ------------------------------------------------------------------ train
    def train(self, ctx: ProcessContext, **kw: Any) -> list[Any]:
        cfg = executor_config(kw["arch"], kw.get("variant", "smoke"),
                              bool(kw.get("use_pallas", False)))
        steps = int(kw.get("steps", 10))
        batch_size = int(kw.get("batch", 4))
        seq_len = int(kw.get("seq_len", 64))
        run = kw.get("run", "run0")
        tcfg = TrainConfig(
            optimizer=kw.get("optimizer", "adamw"),
            learning_rate=float(kw.get("learning_rate", 3e-4)),
            warmup_steps=int(kw.get("warmup_steps", 10)),
            total_steps=steps,
            microbatches=int(kw.get("microbatches", 1)),
            checkpoint_every=int(kw.get("checkpoint_every", 5)),
            seed=int(kw.get("seed", 0)),
        )
        ckpt = CheckpointManager(self.cfs, self.colonyname, run=run)
        data = SyntheticTokens(cfg, batch_size, seq_len, seed=tcfg.seed)

        params = _init_params(cfg, tcfg.seed)
        state = init_state(params, tcfg)
        start = 0
        restored = ckpt.restore_latest(state)
        if restored is not None:
            state, start = restored
            start += 1  # resume after the checkpointed step
        step_fn = jax.jit(make_train_step(cfg, tcfg))

        last_metrics: dict = {}
        for step in range(start, steps):
            if self.die_at_step is not None and step == self.die_at_step:
                self.die_at_step = None  # a respawned clone must survive
                raise SimulatedCrash(f"chaos at step {step}")
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(step).items()}
            state, metrics = step_fn(state, batch)
            last_metrics = {k: float(v) for k, v in metrics.items()}
            if (step + 1) % tcfg.checkpoint_every == 0 or step == steps - 1:
                ckpt.save(state, step, async_=False)
        return [{"final_step": steps - 1, "metrics": last_metrics, "run": run}]

    # --------------------------------------------------------------- evaluate
    def evaluate(self, ctx: ProcessContext, **kw: Any) -> list[Any]:
        cfg = executor_config(kw["arch"], kw.get("variant", "smoke"),
                              bool(kw.get("use_pallas", False)))
        run = kw.get("run", "run0")
        batch_size = int(kw.get("batch", 4))
        seq_len = int(kw.get("seq_len", 64))
        batches = int(kw.get("eval_batches", 2))
        tcfg = TrainConfig(seed=int(kw.get("seed", 0)))
        ckpt = CheckpointManager(self.cfs, self.colonyname, run=run)
        params = _init_params(cfg, tcfg.seed)
        state = init_state(params, tcfg)
        restored = ckpt.restore_latest(state)
        if restored is None:
            raise RuntimeError(f"no checkpoint for run {run}")
        state, step = restored
        eval_fn = jax.jit(make_eval_step(cfg, tcfg))
        data = SyntheticTokens(cfg, batch_size, seq_len, seed=9999)
        ces = []
        for i in range(batches):
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
            ces.append(float(eval_fn(state["params"], batch)["ce"]))
        return [{"step": step, "eval_ce": float(np.mean(ces)), "run": run}]


class ServeExecutor(JaxExecutorBase):
    """Hosts a ServeEngine; handles generator-fired ``generate_batch``."""

    def __init__(self, *args: Any, arch: str = "stablelm-3b", variant: str = "smoke",
                 max_len: int = 128, run: str | None = None, **kw: Any) -> None:
        super().__init__(*args, **kw)
        from ..serve.batcher import make_batch_handler
        from ..serve.engine import ServeEngine

        cfg = executor_config(arch, variant)
        params = _init_params(cfg, 0)
        if run is not None:  # serve a trained checkpoint (continuum hand-off)
            from ..train.train_step import init_state as _init

            ckpt = CheckpointManager(self.cfs, self.colonyname, run=run)
            tcfg = TrainConfig()
            restored = ckpt.restore_latest(_init(params, tcfg))
            if restored is not None:
                params = restored[0]["params"]
        self.engine = ServeEngine(cfg, params, max_len=max_len)
        self.register_function(
            "generate_batch", make_batch_handler(self.engine, self.cfs, self.colonyname)
        )


class DataExecutor(JaxExecutorBase):
    """'Edge' executor: ingests (synthesizes) raw data into CFS."""

    def __init__(self, *args: Any, **kw: Any) -> None:
        super().__init__(*args, **kw)
        self.register_function("prepare_data", self.prepare_data)

    def prepare_data(self, ctx: ProcessContext, **kw: Any) -> list[Any]:
        import json

        shards = int(kw.get("shards", 2))
        tokens_per_shard = int(kw.get("tokens_per_shard", 1024))
        label = kw.get("label", "/datasets/synth")
        rng = np.random.default_rng(int(kw.get("seed", 0)))
        uploaded = []
        for i in range(shards):
            toks = rng.integers(0, int(kw.get("vocab", 256)), tokens_per_shard, dtype=np.int32)
            meta = self.cfs.upload_bytes(
                self.colonyname, label, f"shard-{i:04d}.bin", toks.tobytes()
            )
            uploaded.append(meta["fileid"])
        snap = self.cfs.client.create_snapshot(
            self.colonyname, label, kw.get("snapshot_name", "dataset-v1"), self.prvkey
        )
        return [{"snapshotid": snap["snapshotid"], "files": len(uploaded)}]
