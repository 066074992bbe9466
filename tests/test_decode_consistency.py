"""prefill+decode == full forward, per family (the serving contract)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.data.pipeline import SyntheticTokens
from repro.models import decode_step, forward, init_params, model_spec, prefill

B, S = 2, 16


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_forward(arch):
    cfg = get_config(arch, "smoke").copy(param_dtype="float32", compute_dtype="float32")
    params = init_params(jax.random.key(0), model_spec(cfg), jnp.float32)
    src = SyntheticTokens(cfg, B, S + 2, seed=3)
    batch = {k: jnp.asarray(v) for k, v in src.batch_at(0).items()}
    tokens = batch["tokens"]

    logits, _ = forward(params, cfg, batch)
    pre = dict(batch)
    pre["tokens"] = tokens[:, :S]
    last, cache = prefill(params, cfg, pre, max_len=S + 4)
    np.testing.assert_allclose(
        np.asarray(last), np.asarray(logits[:, S - 1 : S]), atol=2e-2, rtol=1e-3
    )
    # two consecutive decode steps
    dl, cache = decode_step(params, cfg, tokens[:, S : S + 1], cache, jnp.int32(S))
    np.testing.assert_allclose(
        np.asarray(dl)[:, 0], np.asarray(logits[:, S]), atol=2e-2, rtol=1e-3
    )
    dl2, _ = decode_step(params, cfg, tokens[:, S + 1 : S + 2], cache, jnp.int32(S + 1))
    np.testing.assert_allclose(
        np.asarray(dl2)[:, 0], np.asarray(logits[:, S + 1]), atol=3e-2, rtol=1e-3
    )


def test_swa_ring_buffer_long_decode():
    """Mixtral-style rolling cache: decoding past the window stays exact."""
    cfg = get_config("mixtral-8x7b", "smoke").copy(
        param_dtype="float32", compute_dtype="float32"
    )
    assert cfg.sliding_window == 8
    params = init_params(jax.random.key(0), model_spec(cfg), jnp.float32)
    total = 24  # 3x the window
    tokens = jax.random.randint(jax.random.key(5), (B, total), 0, cfg.vocab_size)
    logits, _ = forward(params, cfg, {"tokens": tokens})
    # prefill the first 4 (< window), then decode one by one past the window
    _, cache = prefill(params, cfg, {"tokens": tokens[:, :4]}, max_len=total)
    for pos in range(4, total):
        dl, cache = decode_step(params, cfg, tokens[:, pos : pos + 1], cache, jnp.int32(pos))
        np.testing.assert_allclose(
            np.asarray(dl)[:, 0], np.asarray(logits[:, pos]), atol=3e-2, rtol=1e-3,
            err_msg=f"divergence at pos {pos}",
        )


@pytest.mark.parametrize("arch,path", [
    ("stablelm-3b", "kernel"),  # contiguous MHA cache: the Pallas decode kernel
    ("mixtral-8x7b", "xla"),  # sliding-window ring buffer
    ("deepseek-v3-671b", "xla"),  # MLA latent cache
])
def test_engine_donated_cache_matches_undonated(arch, path, monkeypatch):
    """ServeEngine donates the cache to each decode step, as on the chip;
    two generate calls give the tokens of an undonated prefill/decode loop,
    and each decode call is counted once under the path the config takes."""
    from repro.serve import engine as engine_mod
    from repro.serve.engine import ServeEngine, make_prefill, make_serve_step

    monkeypatch.setattr(engine_mod, "donated_cache", lambda: (2,))

    cfg = get_config(arch, "smoke")
    params = init_params(jax.random.key(0), model_spec(cfg), jnp.dtype(cfg.param_dtype))
    new, max_len = 4, 32
    tokens = np.asarray(jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size))

    prefill_fn = jax.jit(make_prefill(cfg, max_len))
    step = jax.jit(make_serve_step(cfg))
    logits, cache = prefill_fn(params, {"tokens": jnp.asarray(tokens)})
    want = [jnp.argmax(logits[:, -1], -1)]
    for i in range(new - 1):
        logits, cache = step(params, want[-1][:, None].astype(jnp.int32), cache, jnp.int32(S + i))
        want.append(jnp.argmax(logits[:, -1], -1))
    want = np.stack([np.asarray(t) for t in want], axis=1)

    engine = ServeEngine(cfg, params, max_len=max_len)
    assert engine.decode_path == path
    _, donated = engine._prefill(params, {"tokens": jnp.asarray(tokens)})
    engine._decode(params, jnp.asarray(want[:, :1], jnp.int32), donated, jnp.int32(S))
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(donated))
    for _ in range(2):
        np.testing.assert_array_equal(engine.generate(tokens, max_new_tokens=new), want)
    calls = 2 * (new - 1)
    other = "xla" if path == "kernel" else "kernel"
    assert engine.stats[f"decode_{path}"] == calls and engine.stats[f"decode_{other}"] == 0
    from repro.core import tracing

    assert tracing.counters()[f"engine.decode.{path}"]["n"] >= calls


def test_decode_depth_follows_the_parameters():
    """A configuration that names more layers than its parameters hold (the
    chip benchmark serves a 10-layer cut under the 40-layer granite config)
    decodes through the layers the parameters give, as prefill does."""
    from repro.serve.engine import ServeEngine

    cfg = get_config("granite-3-8b", "smoke")
    cut = cfg.copy(num_layers=1)
    params = init_params(jax.random.key(0), model_spec(cut), jnp.dtype(cfg.param_dtype))
    tokens = np.asarray(jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size))
    assert cfg.num_layers > cut.num_layers
    np.testing.assert_array_equal(ServeEngine(cfg, params, max_len=32).generate(tokens, 4),
                                  ServeEngine(cut, params, max_len=32).generate(tokens, 4))
