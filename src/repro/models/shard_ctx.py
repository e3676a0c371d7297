"""Ambient activation-sharding context for model code.

The launcher (dryrun / train / serve) declares the mesh axes once; model
code sprinkles `constrain(x, dims)` on the tensors whose sharding GSPMD
tends to get wrong without help (MoE dispatch buffers, big-vocab logits,
post-embedding activations). When no context is set (unit tests, single
device) every constraint is a no-op.
"""

from __future__ import annotations

import contextlib

import jax
from jax.sharding import PartitionSpec as P

_CTX: dict | None = None

__all__ = ["activation_sharding", "constrain", "dp", "tp"]


def _mesh_shape():
    """Axis-name -> size of the ambient mesh."""
    return jax.sharding.get_abstract_mesh().shape


@contextlib.contextmanager
def activation_sharding(dp_axes: tuple[str, ...], model_axis: str = "model"):
    global _CTX
    prev = _CTX
    _CTX = {"dp": tuple(dp_axes), "tp": model_axis}
    try:
        yield
    finally:
        _CTX = prev


def dp():
    return _CTX["dp"] if _CTX else None


def tp():
    return _CTX["tp"] if _CTX else None


def dp_size() -> int:
    if _CTX is None:
        return 1
    shape = _mesh_shape()
    n = 1
    for a in _CTX["dp"]:
        n *= shape[a]
    return n


def tp_size() -> int:
    if _CTX is None:
        return 1
    return _mesh_shape()[_CTX["tp"]]


def constrain(x, dims):
    """dims: tuple over x's axes of 'dp' | 'tp' | 'dpt' (dp+tp combined) |
    None. Axes that don't divide the dim are dropped. No-op w/o context."""
    if _CTX is None:
        return x
    mesh_shape = _mesh_shape()
    spec = []
    for d, size in zip(dims, x.shape):
        if d is None:
            spec.append(None)
            continue
        axes = {"dp": _CTX["dp"], "tp": (_CTX["tp"],),
                "dpt": tuple(_CTX["dp"]) + (_CTX["tp"],)}[d]
        total = 1
        for a in axes:
            total *= mesh_shape[a]
        spec.append(axes if size % total == 0 else None)
    return jax.lax.with_sharding_constraint(x, P(*spec))
