"""Plain references the benchmark's check compares the program with. They
import nothing of the program.

A scorer configuration names its reference: the ``scorer`` block's
``reference`` is the name of a module of this package, which
``scorer_reference`` imports. There is no default. Such a module defines:

- ``row_losses(sc, texts, seq, quantize=None) -> np.ndarray``: each text's
  mean next-token loss over its scored positions, in float32 at the
  ``"highest"`` matmul precision; ``quantize`` names the dtype the control
  rounds every matmul operand to;
- ``real_tokens(text, seq) -> int``: the positions scored for one text;
- ``flops_per_token(sc) -> int``: forward FLOPs per real token of the model
  as ``sc`` configures it;
- ``TINY``: the ``scorer`` keys that shrink the architecture for CPU tests.

``sc`` is the configuration's ``scorer`` block. A second architecture comes
as new files: its configuration, its module here, their tests.
"""
from __future__ import annotations

import importlib
from types import ModuleType
from typing import Any, Dict

SCORER_INTERFACE = ("row_losses", "real_tokens", "flops_per_token", "TINY")


def scorer_reference(sc: Dict[str, Any]) -> ModuleType:
    """The reference module the scorer block ``sc`` names; a block that names
    none (``KeyError``), or a module that is not here
    (``ModuleNotFoundError``) or lacks a name of the interface
    (``ValueError``), is an error."""
    mod = importlib.import_module(f"{__name__}.{sc['reference']}")
    missing = [n for n in SCORER_INTERFACE if not hasattr(mod, n)]
    if missing:
        raise ValueError(f"bench.reference.{sc['reference']} is no scorer reference: "
                         f"it lacks {', '.join(missing)}")
    return mod
