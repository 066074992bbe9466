"""Smoke run of the colony's serving path on one TPU chip.

    python chip_smoke.py

Serves stablelm-3b at its published widths, in the config's bf16, with
random weights from a seed, through the normal path: client → generator →
assign → ServeExecutor → ServeEngine → CFS result. It checks every answer,
compares one decode step with the full forward pass, and prints as its last
line one JSON object naming the device. It exits non-zero, without that
line, when the device is not a TPU or when any phase fails. Everything runs
in this one process, which holds the chip.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ARCH, VARIANT = "stablelm-3b", "full"
REQUESTS, BATCH, PROMPT_LEN, NEW_TOKENS, MAX_LEN = 8, 4, 128, 32, 512
# Logits come out of a bf16 matmul: allow this many bf16 ulps of the
# largest reference logit between the served decode step and forward.
TOL_ULPS = 8


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"smoke check failed: {what}")


def decode_vs_forward(engine, prompt: list[int]) -> tuple[float, float]:
    """Max |decode - forward| over the first decode step's float32 logits
    for one prompt, and the tolerance it is held to."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.model import forward
    from repro.serve.engine import make_prefill, make_serve_step

    cfg, params = engine.cfg, engine.params
    tokens = jnp.asarray([prompt], jnp.int32)
    last, cache = jax.jit(make_prefill(cfg, MAX_LEN))(params, {"tokens": tokens})
    tok = jnp.argmax(last[:, -1], axis=-1)[:, None].astype(jnp.int32)
    got, _ = jax.jit(make_serve_step(cfg))(params, tok, cache, jnp.int32(len(prompt)))
    full = jnp.concatenate([tokens, tok], axis=1)
    ref, _ = jax.jit(lambda p, b: forward(p, cfg, b))(params, {"tokens": full})
    got = np.asarray(got[0, -1], np.float32)
    ref = np.asarray(ref[0, -1], np.float32)
    for name, x in (("prefill", np.asarray(last, np.float32)), ("decode", got),
                    ("forward", ref)):
        check(np.isfinite(x).all(), f"{name} logits are not finite")
    top = float(np.abs(ref).max())
    tol = TOL_ULPS * 2.0 ** (np.floor(np.log2(top)) - 7)  # bf16: 8 mantissa bits
    return float(np.abs(got - ref).max()), float(tol)


def main() -> int:
    from repro.launch.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    import jax

    devices = jax.devices()
    print(f"jax {jax.__version__}  devices {devices}  compile cache {cache_dir}")
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 1
    print(f"device_kind {dev.device_kind}")

    from repro.launch.serve import serve

    served = serve(arch=ARCH, variant=VARIANT, requests=REQUESTS, batch_size=BATCH,
                   prompt_len=PROMPT_LEN, max_new_tokens=NEW_TOKENS,
                   max_len=MAX_LEN)
    engine = served.engine
    vocab = engine.cfg.vocab_size
    check(engine.cfg.param_dtype == "bfloat16", f"params in {engine.cfg.param_dtype}")
    check(len(served.outputs) == REQUESTS, f"{len(served.outputs)} answers")
    for i, out in enumerate(served.outputs):
        check(len(out) == NEW_TOKENS, f"request {i}: {len(out)} tokens")
        check(all(0 <= t < vocab for t in out), f"request {i}: token out of range")
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(engine.params))
    st = engine.stats
    print(f"compile (first call) prefill {served.warmup_s['prefill_s']:.2f}s  "
          f"decode {served.warmup_s['decode_s']:.2f}s")
    print(f"param bytes {param_bytes}")
    print(f"served {st['requests']} requests in {st['batches']} batches, "
          f"{st['tokens']} tokens; engine {st['seconds'] / st['batches']:.3f}s/batch, "
          f"submit-to-last-result {served.seconds:.2f}s")

    diff, tol = decode_vs_forward(engine, served.prompts[0])
    print(f"decode vs forward: max |diff| {diff:.6f}  tolerance {tol:.6f}")
    check(diff <= tol, f"decode step differs from forward by {diff} > {tol}")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"peak_bytes_in_use {peak}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
