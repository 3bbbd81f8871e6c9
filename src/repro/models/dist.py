"""Distribution context shared by model code.

Model forward functions are written once and work in two modes:

* local (no mesh): smoke tests / single-device examples — plain jnp, MoE uses
  the local dispatch path.
* distributed (mesh set): the launcher installs a mesh + logical axis
  assignment here; MoE switches to the expert-parallel ``shard_map`` path and
  activation sharding constraints become active.

This avoids threading mesh handles through every call site while keeping
``jax.jit`` tracing pure (the context is read at trace time).
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


@dataclass
class DistContext:
    mesh: Optional[Mesh] = None
    # logical axis name -> mesh axis name(s)
    batch_axes: Optional[Sequence[str]] = ("data",)   # batch dim of activations
    model_axes: Optional[Sequence[str]] = ("model",)  # tensor-parallel dim
    # None batch_axes => batch replicated (e.g. long_500k with B=1)

    @property
    def active(self) -> bool:
        return self.mesh is not None

    def axis_size(self, axes) -> int:
        if not self.active or axes is None:
            return 1
        n = 1
        for a in axes:
            n *= self.mesh.shape[a]
        return n


_CTX = DistContext()


def get_ctx() -> DistContext:
    return _CTX


def set_mesh(mesh: Optional[Mesh], batch_axes=("data",), model_axes=("model",)) -> None:
    global _CTX
    _CTX = DistContext(mesh=mesh, batch_axes=tuple(batch_axes) if batch_axes else None,
                       model_axes=tuple(model_axes) if model_axes else None)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], batch_axes=("data",), model_axes=("model",)):
    global _CTX
    prev = _CTX
    set_mesh(mesh, batch_axes, model_axes)
    try:
        yield _CTX
    finally:
        _CTX = prev


def constrain(x: jnp.ndarray, *spec) -> jnp.ndarray:
    """with_sharding_constraint that is a no-op without a mesh."""
    ctx = get_ctx()
    if not ctx.active:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(ctx.mesh, P(*spec)))


def batch_spec_entry():
    """PartitionSpec entry for the activation batch dimension."""
    ctx = get_ctx()
    if not ctx.active or ctx.batch_axes is None:
        return None
    return tuple(ctx.batch_axes) if len(ctx.batch_axes) > 1 else ctx.batch_axes[0]


def model_spec_entry():
    ctx = get_ctx()
    if not ctx.active or ctx.model_axes is None:
        return None
    return tuple(ctx.model_axes) if len(ctx.model_axes) > 1 else ctx.model_axes[0]


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off: every call
    site in this repo returns a mix of per-shard and replicated values."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
