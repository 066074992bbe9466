"""The block a configuration names, and what a block module gives.

A configuration file names its block (``"block": "dense"``), and the
harness loads ``benchmarks/chip/blocks/<block>.py`` by that name, as it
loads ``metrics/<name>.py`` for a per-layer metric. Nothing else in the
harness knows a block's shapes, tensors or work, so a new architecture
comes in as a new block file, never as an edit of the harness.

A block module gives:

- ``Dims``: a frozen dataclass of the block's sizes, with a ``vocab``
  field (the load generator draws token ids below it) and a classmethod
  ``from_config(cfg)`` that reads a configuration file's published keys
  as run. It is hashable: jitted functions take it as a static argument.
- ``dims_of(mc)``: the ``Dims`` of the program's ``ModelConfig``; raises
  ``ValueError`` when ``mc`` is not this block.
- ``layout(dims)``: name -> (shape, kind, scale) of every weight, for
  ``weights.make``. It may hold several layer stacks.
- ``program_params(w, dims, mc)``: those weights in the program's
  parameter tree, checked leaf by leaf (``system.checked``).
- ``logits(w, dims, tokens, start, quant=False)``: the plain float32
  reference's logits (B, T - start, V) of positions start..T-1 of
  ``tokens`` (B, T); ``quant=True`` is its fp8 control. It imports
  nothing of the program; ``reference.py`` has the helpers it may share.
- ``flops(dims, kind, batch, n)`` and ``nbytes(dims, kind, batch, n)``:
  the operations and bytes one served call needs, for ``kind``
  "prefill" (``n`` the prompt length) or "decode" (``n`` the position).
"""

from __future__ import annotations

import importlib.util
import re
import sys
from types import ModuleType

from .cell import HERE

DIR = HERE / "blocks"
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_-]*$")


def load(name: str) -> ModuleType:
    """The module ``blocks/<name>.py``, loaded once per process (its
    ``Dims`` is then one class, so equal sizes compare equal)."""
    path = DIR / f"{name}.py"
    if not _NAME.match(name) or not path.is_file():
        raise ValueError(f"no block {name!r}: {path} does not exist")
    module = "chipbench_block_" + name.replace("-", "_")
    if module not in sys.modules:
        spec = importlib.util.spec_from_file_location(module, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[module] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[module]
            raise
    return sys.modules[module]


def of(config: dict) -> ModuleType:
    """The block module that a configuration names."""
    if "block" not in config:
        raise ValueError(f"configuration {config.get('name')!r} names no block: its file needs "
                         f"\"block\": \"<name>\" for {DIR}/<name>.py")
    return load(config["block"])
