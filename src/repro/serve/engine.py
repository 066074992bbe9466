"""Serving engine: jitted prefill + decode with sampling.

``serve_step`` (decode one token for the whole batch against the KV/state
cache) is the function the decode_32k / long_500k cells lower on the
production mesh. On-device sampling keeps the decode loop host-free
except for the final token fetch.
"""

from __future__ import annotations

import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..models.model import decode_step, forward, pad_cache, prefill


def make_serve_step(cfg: ModelConfig):
    """(params, tokens(B,1), cache, pos) -> (logits(B,1,V), cache)."""

    def serve_step(params, tokens, cache, pos):
        return decode_step(params, cfg, tokens, cache, pos)

    return serve_step


def make_prefill(cfg: ModelConfig, max_len: int | None = None):
    def prefill_fn(params, batch):
        return prefill(params, cfg, batch, max_len=max_len)

    return prefill_fn


def sample_token(logits: jnp.ndarray, rng: jax.Array, temperature: float) -> jnp.ndarray:
    """logits: (B,1,V) -> (B,1) int32."""
    if temperature <= 0.0:
        return jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    return jax.random.categorical(rng, logits[:, -1] / temperature, axis=-1)[
        :, None
    ].astype(jnp.int32)


class ServeEngine:
    """Host-side generation loop over the jitted prefill/decode steps."""

    def __init__(self, cfg: ModelConfig, params: Any, max_len: int = 256):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self._prefill = jax.jit(make_prefill(cfg, max_len))
        self._decode = jax.jit(make_serve_step(cfg))
        self._sample = jax.jit(sample_token, static_argnums=(2,))
        self.stats = {"requests": 0, "tokens": 0, "batches": 0, "seconds": 0.0}

    def warmup(self, batch: int, prompt_len: int, max_new_tokens: int) -> dict[str, float]:
        """Compile everything one batch shape needs, so that serving it
        compiles nothing; returns the seconds of the first prefill and the
        first decode call (compilation included)."""
        tokens = np.zeros((batch, prompt_len), np.int32)
        t0 = time.perf_counter()
        logits, cache = jax.block_until_ready(
            self._prefill(self.params, {"tokens": jnp.asarray(tokens)}))
        t1 = time.perf_counter()
        tok = self._sample(logits, jax.random.key(0), 0.0)
        jax.block_until_ready(self._decode(self.params, tok, cache, jnp.int32(prompt_len)))
        t2 = time.perf_counter()
        self._generate(tokens, max_new_tokens)  # the loop's small eager programs
        return {"prefill_s": t1 - t0, "decode_s": t2 - t1}

    def generate(
        self,
        tokens: np.ndarray,  # (B, S) right-aligned prompts (no padding support needed for synthetic)
        max_new_tokens: int = 16,
        temperature: float = 0.0,
        seed: int = 0,
        extras: dict | None = None,
    ) -> np.ndarray:
        b, s = tokens.shape
        assert s + max_new_tokens <= self.max_len, "increase max_len"
        t0 = time.perf_counter()
        out = self._generate(tokens, max_new_tokens, temperature, seed, extras)
        self.stats["requests"] += b
        self.stats["tokens"] += b * max_new_tokens
        self.stats["batches"] += 1
        self.stats["seconds"] += time.perf_counter() - t0
        return out

    def _generate(self, tokens: np.ndarray, max_new_tokens: int, temperature: float = 0.0,
                  seed: int = 0, extras: dict | None = None) -> np.ndarray:
        batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
        if extras:
            batch.update({k: jnp.asarray(v) for k, v in extras.items()})
        logits, cache = self._prefill(self.params, batch)
        rng = jax.random.key(seed)
        out = []
        tok = self._sample(logits, rng, temperature)
        out.append(tok)
        pos = tokens.shape[1]
        for i in range(max_new_tokens - 1):
            rng, sub = jax.random.split(rng)
            logits, cache = self._decode(self.params, tok, cache, jnp.int32(pos))
            tok = self._sample(logits, sub, temperature)
            out.append(tok)
            pos += 1
        return np.asarray(jnp.concatenate(out, axis=1))
