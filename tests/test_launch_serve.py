"""Entry points of the serving path: the executors' model config, the
compile-cache placement, ``launch.serve.serve`` and ``chip_smoke.py``."""

import os
import sys
import time

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch.compile_cache import place_compile_cache
from repro.launch.serve import ServeFailed, serve
from repro.runtime.jax_executor import executor_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def jax_cache_config():
    """Put JAX's cache settings back as they were after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("variant,dtype", [("smoke", "float32"), ("full", "bfloat16")])
def test_executor_config_dtypes(variant, dtype):
    cfg = executor_config("stablelm-3b", variant)
    assert cfg.param_dtype == cfg.compute_dtype == dtype
    assert not cfg.use_pallas
    assert executor_config("stablelm-3b", variant, use_pallas=True).use_pallas
    if variant == "full":
        assert (cfg.num_layers, cfg.d_model, cfg.vocab_size) == (32, 2560, 50304)


def test_compile_cache_leaves_external_dir_alone(jax_cache_config, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert place_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == jax_cache_config


def test_compile_cache_defaults_to_checkout(jax_cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert place_compile_cache() == want
    assert place_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_serve_answers_every_request():
    served = serve(requests=4, batch_size=2, prompt_len=8, max_new_tokens=4, max_len=32)
    assert [len(p) for p in served.prompts] == [8] * 4
    assert [len(o) for o in served.outputs] == [4] * 4
    vocab = served.engine.cfg.vocab_size
    assert all(0 <= t < vocab for o in served.outputs for t in o)
    assert served.engine.stats["requests"] == 4
    assert set(served.warmup_s) == {"prefill_s", "decode_s"}


def test_serve_fails_fast_when_a_batch_fails(monkeypatch):
    from repro.serve.engine import ServeEngine

    def broken(self, *a, **k):
        raise RuntimeError("injected handler failure")

    monkeypatch.setattr(ServeEngine, "generate", broken)
    t0 = time.monotonic()
    with pytest.raises(ServeFailed, match="injected handler failure"):
        serve(requests=2, batch_size=2, prompt_len=8, max_new_tokens=4, max_len=32)
    assert time.monotonic() - t0 < 60  # the error, not the request timeout


def test_chip_smoke_refuses_the_cpu(jax_cache_config, monkeypatch, capsys):
    monkeypatch.syspath_prepend(ROOT)
    import chip_smoke

    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert "needs a TPU" in err
    assert '"ok"' not in out
    sys.modules.pop("chip_smoke", None)
