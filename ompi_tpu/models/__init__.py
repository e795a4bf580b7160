"""Flagship models built on the framework's parallel primitives.

Lazy re-exports keep ``import ompi_tpu.models`` free of jax imports;
submodules load on first attribute access.
"""

from __future__ import annotations

import importlib
from typing import Any

_LAZY = {
    "TransformerConfig": ("ompi_tpu.models.transformer",
                          "TransformerConfig"),
    "FLAGSHIP": ("ompi_tpu.models.transformer", "FLAGSHIP"),
    "FLAGSHIP_BATCH": ("ompi_tpu.models.transformer", "FLAGSHIP_BATCH"),
    "init_params": ("ompi_tpu.models.transformer", "init_params"),
    "make_train_step": ("ompi_tpu.models.transformer", "make_train_step"),
    "make_train_loop": ("ompi_tpu.models.transformer", "make_train_loop"),
    "make_forward": ("ompi_tpu.models.transformer", "make_forward"),
    "make_loss_fn": ("ompi_tpu.models.transformer", "make_loss_fn"),
    "make_decoder": ("ompi_tpu.models.decode", "make_decoder"),
    "ArraySource": ("ompi_tpu.models.data", "ArraySource"),
    "MemmapSource": ("ompi_tpu.models.data", "MemmapSource"),
    "train_stream": ("ompi_tpu.models.data", "train_stream"),
}

__all__ = sorted(_LAZY)


def __getattr__(name: str) -> Any:
    try:
        mod, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(f"module {__name__!r} has no attribute "
                             f"{name!r}") from None
    return getattr(importlib.import_module(mod), attr)
