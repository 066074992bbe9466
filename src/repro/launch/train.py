"""Training launcher: ``--arch <id>`` runs the colony-dispatched training
loop (smoke variant on CPU; full variant is what the dry-run lowers).

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-3b --steps 20

The process is submitted as a ColonyOS function specification and
executed by a TrainerExecutor — the same path the continuum uses — so
checkpointing, lease-based fault tolerance and CFS hand-off all apply.
"""

from __future__ import annotations

import argparse
import json


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--variant", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "adafactor"])
    ap.add_argument("--learning-rate", type=float, default=3e-4)
    ap.add_argument("--checkpoint-every", type=int, default=10)
    ap.add_argument("--run", default="cli-run")
    ap.add_argument("--use-pallas", action="store_true")
    args = ap.parse_args()

    from repro.core import Colonies, Crypto, FunctionSpec, InProcTransport
    from repro.core.cluster import standalone_server
    from repro.core.fs import MemoryStorage
    from repro.launch.compile_cache import place_compile_cache
    from repro.runtime.jax_executor import TrainerExecutor

    place_compile_cache()

    server_prv, colony_prv = Crypto.prvkey(), Crypto.prvkey()
    server = standalone_server(Crypto.id(server_prv))
    server.start_background(failsafe_interval=0.2)
    client = Colonies(InProcTransport([server]))
    client.add_colony("launch", Crypto.id(colony_prv), server_prv)
    trainer = TrainerExecutor(client, "launch", "trainer-0", "tpu-pod",
                              MemoryStorage(), colony_prvkey=colony_prv)
    trainer.start(poll_timeout=0.2)

    spec = FunctionSpec.from_dict({
        "conditions": {"colonyname": "launch", "executortype": "tpu-pod"},
        "funcname": "train",
        "kwargs": {
            "arch": args.arch, "variant": args.variant, "steps": args.steps,
            "batch": args.batch, "seq_len": args.seq_len,
            "microbatches": args.microbatches, "optimizer": args.optimizer,
            "learning_rate": args.learning_rate,
            "checkpoint_every": args.checkpoint_every, "run": args.run,
            "use_pallas": args.use_pallas,
        },
        "maxexectime": 24 * 3600, "maxretries": 3,
    })
    p = client.submit(spec, colony_prv)
    done = client.wait(p["processid"], colony_prv, timeout=24 * 3600)
    print(json.dumps(done["out"], indent=1))
    trainer.stop()
    server.stop()
    if done["state"] != "successful":
        raise SystemExit(1)


if __name__ == "__main__":
    main()
