"""Compile each cell's prefill and decode at its largest warmed batch
for a described TPU v5e, without a chip, and print memory_analysis().

    JAX_PLATFORMS=cpu python benchmarks/chip/compile_v5e.py stablelm-3b.chat granite-3-8b-stage.rag
    JAX_PLATFORMS=cpu python benchmarks/chip/compile_v5e.py --layers 20 granite-3-8b-stage.rag

What the chip's compiler refuses here costs no chip time. It counts one
program at a time, not what else the process holds on the device.
``--layers`` compiles the configuration at another depth, to find the
depth that one chip can hold.
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main(workloads: list[str], layers: int | None = None) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import system
    from chipbench.cell import load
    from repro.models import model_spec
    from repro.models.model import abstract_cache
    from repro.models.sharding import abstract_params
    from repro.serve.engine import make_prefill, make_serve_step

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    put = lambda t: jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), t)
    for name in workloads:
        cell = load(name)
        mc = system.model_config(cell.config, "full")
        if layers is not None:
            mc = mc.copy(num_layers=layers)
        tr = cell.traffic
        b = int(tr["generator"]["queuesize"]) + 2
        s, max_len = int(tr["prompt_tokens"]), int(tr["max_len"])
        params = put(abstract_params(model_spec(mc), jnp.dtype(mc.param_dtype)))
        tokens = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one)
        cache = put(abstract_cache(mc, b, max_len))
        for what, fn, args in (
            ("prefill", make_prefill(mc, max_len), (params, {"tokens": tokens})),
            ("decode", make_serve_step(mc), (params, jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=one),
                                            cache, jax.ShapeDtypeStruct((), jnp.int32, sharding=one))),
        ):
            t = time.perf_counter()
            compiled = jax.jit(fn).lower(*args).compile()
            m = compiled.memory_analysis()
            print(f"{name} {what} layers={mc.num_layers} B={b} S={s} max_len={max_len}: compile {time.perf_counter() - t:.1f} s; "
                  f"arguments {m.argument_size_in_bytes} B, outputs {m.output_size_in_bytes} B, "
                  f"temporaries {m.temp_size_in_bytes} B, aliased {m.alias_size_in_bytes} B", flush=True)


if __name__ == "__main__":
    args = sys.argv[1:]
    depth = None
    if args[:1] == ["--layers"]:
        depth, args = int(args[1]), args[2:]
    main(args, depth)
