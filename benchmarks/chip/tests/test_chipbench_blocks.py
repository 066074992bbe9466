"""The block interface of the chip harness: the loader, a harness that
knows no block's shapes, and the dense block giving the same weights,
reference logits and work counts as the harness gave before it had
blocks (the literal values below were taken from that harness)."""

import ast
import dataclasses
import hashlib
import json
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parents[1] / "src")]

from chipbench import blocks, system, weights  # noqa: E402
from chipbench.cell import HERE  # noqa: E402

CONTRACT = ("Dims", "dims_of", "layout", "program_params", "logits", "flops", "nbytes")
NEUTRAL = ("harness.py", "check.py", "readings.py", "system.py", "work.py")

dense = blocks.load("dense")

# smoke widths of the two configurations' architectures
SMOKE = {
    "stablelm": dense.Dims(layers=2, d_model=64, heads=4, kv_heads=4, head_dim=16, d_ff=176,
                           vocab=256, tied=False, norm="layernorm", norm_eps=1e-5,
                           rope_theta=1e4),
    "granite": dense.Dims(layers=2, d_model=64, heads=4, kv_heads=2, head_dim=16, d_ff=208,
                          vocab=259, tied=True, norm="rmsnorm", norm_eps=1e-5, rope_theta=1e4),
}


def config(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def test_loader_finds_a_block_by_name():
    assert blocks.load("dense") is dense  # once per process: one Dims class
    for name in ("stablelm-3b", "granite-3-8b-stage"):
        assert blocks.of(config(name)) is dense
    assert all(callable(getattr(dense, f)) for f in CONTRACT)
    assert dataclasses.is_dataclass(dense.Dims) and "vocab" in dense.Dims.__dataclass_fields__


@pytest.mark.parametrize("conf,path", [
    ({"name": "x", "block": "no-such-block"}, "blocks/no-such-block.py"),
    ({"name": "x", "block": "../chipbench/harness"}, "blocks/../chipbench/harness.py"),
    ({"name": "x"}, "blocks/<name>.py"),
])
def test_loader_refuses_an_unknown_block(conf, path):
    with pytest.raises(ValueError) as err:
        blocks.of(conf)
    assert str(HERE / path) in str(err.value)  # names the file it looked for


def _dense_names() -> set[str]:
    """The dense block's fields, weight names and parameter-tree keys."""
    d = SMOKE["stablelm"]  # untied, LayerNorm: every weight the block has
    names = {f.name for f in dataclasses.fields(dense.Dims)}
    names |= {part for key in dense.layout(d) for part in key.split(".")}
    mc = system.model_config(config("stablelm-3b"), "smoke")
    tree = dense.program_params(weights.make(dense.layout(d), 0, jnp.float32), d, mc)

    def keys(t):
        for k, v in t.items():
            yield k
            if isinstance(v, dict):
                yield from keys(v)

    names |= set(keys(tree))
    return names - {"vocab"}  # the one field every block's Dims has


def _used_names(path: Path) -> set[str]:
    """Identifiers, attributes, arguments and the dot-separated parts of
    string constants (docstrings aside) of a module."""
    tree = ast.parse(path.read_text())
    docs = {id(n.body[0].value) for n in ast.walk(tree)
            if isinstance(n, (ast.Module, ast.FunctionDef, ast.ClassDef)) and n.body
            and isinstance(n.body[0], ast.Expr) and isinstance(n.body[0].value, ast.Constant)}
    out = set()
    for n in ast.walk(tree):
        if isinstance(n, ast.Name):
            out.add(n.id)
        elif isinstance(n, ast.Attribute):
            out.add(n.attr)
        elif isinstance(n, (ast.arg, ast.keyword)) and n.arg:
            out.add(n.arg)
        elif isinstance(n, ast.Constant) and isinstance(n.value, str) and id(n) not in docs:
            out |= set(n.value.split("."))
    return out


@pytest.mark.parametrize("module", NEUTRAL)
def test_harness_names_no_block_shape(module, _registry):
    """The next block cannot need an edit of these files."""
    found = _used_names(HERE / "chipbench" / module) & _dense_names()
    assert not found, f"chipbench/{module} names the dense block's {sorted(found)}"


@pytest.mark.parametrize("name,prefill,decode", [
    ("stablelm-3b", ((4, 256), 5241058099200.0, 5931673600),
     ((4, 300), 21725184000, 5985433600)),
    ("granite-3-8b-stage", ((6, 4096), 106176020889600.0, 5595398144),
     ((6, 4100), 30355046400, 5395349504)),
])
def test_dense_work_counts_are_unchanged(name, prefill, decode):
    d = dense.Dims.from_config(config(name))
    for kind, ((batch, n), ops, nbytes) in (("prefill", prefill), ("decode", decode)):
        assert dense.flops(d, kind, batch, n) == ops
        assert dense.nbytes(d, kind, batch, n) == nbytes


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update((a.view(np.uint16) if a.dtype == jnp.bfloat16 else a.view(np.uint8)).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name,want", [
    ("stablelm", ("fd3e08978d2bf1880357bb5cab55d24c862f82b7359699585815eaffcd84e2c3",
                  "6e9829794d700d01ec601824a671bba7b8e14a347b6b06a7791ae28b990fc699",
                  "1a79f9b9e69b914a00decaac412330b5ddc4c84439bc884fb4cf255001861554")),
    ("granite", ("0d15d83c15156d5174a0c0b52c586d8e572d45292bdbd9ee51f36a1519d2412f",
                 "8c1385cafe6d8ed0c79ce09ca8eb7471e56759b5a77216d7d94b6ff6f8f3f00f",
                 "95f157667c61142949a423a3460dc05d4b0de4208892a5a8861aa37e17a70d82")),
])
def test_dense_weights_and_reference_are_unchanged(name, want):
    """Bit for bit, on XLA's CPU backend: the bf16 weights of seed 12345,
    the reference's logits of positions 11..19 and its fp8 control's."""
    d = SMOKE[name]
    w = weights.make(dense.layout(d), 12345)
    toks = jnp.asarray(np.random.default_rng(7).integers(0, d.vocab, (2, 20)), jnp.int32)
    got = (_digest([w[k] for k in sorted(w)]), _digest([dense.logits(w, d, toks, 11)]),
           _digest([dense.logits(w, d, toks, 11, quant=True)]))
    assert got == want


@pytest.fixture()
def _registry():
    from repro.configs import ARCHS

    before = dict(ARCHS)
    yield
    ARCHS.clear()
    ARCHS.update(before)
