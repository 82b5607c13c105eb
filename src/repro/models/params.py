"""Parameter-spec infrastructure.

Models declare their parameters as a pytree of :class:`ParamSpec` (shape, dtype,
*logical axes*, initializer).  This lets us:

  * materialize real arrays (``init_params``) for smoke tests / examples,
  * build ``jax.ShapeDtypeStruct`` stand-ins (``abstract_params``) for the
    multi-pod dry-run without allocating 480B-parameter models,
  * derive ``PartitionSpec`` trees from logical-axis -> mesh-axis rule tables
    (see ``repro.dist.sharding``) for any mesh.

Logical axis vocabulary (used by the sharding rules):
  "embed"     d_model
  "vocab"     vocabulary
  "heads"     attention query heads
  "kv_heads"  attention kv heads
  "head_dim"  per-head dim
  "mlp"       ffn hidden
  "expert"    MoE expert axis
  "kv_lora"   MLA latent dim
  "inner"     SSM / RG-LRU inner width
  "state"     SSM state dim
  "conv"      short conv width
  "layers"    stacked (scanned) layer axis -- never sharded
  None        replicated axis
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Declaration of one parameter tensor."""

    shape: tuple[int, ...]
    dtype: Any = jnp.bfloat16
    axes: tuple[str | None, ...] = ()
    init: str = "normal"          # normal | zeros | ones | scaled_normal | embed
    init_scale: float | None = None  # stddev override

    def __post_init__(self):
        if len(self.axes) != len(self.shape):
            raise ValueError(
                f"axes {self.axes} rank mismatch with shape {self.shape}")

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1

    def abstract(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


@functools.partial(jax.jit, static_argnums=0)
def _init_one(spec: ParamSpec, key: jax.Array) -> jax.Array:
    # one compiled program per distinct spec: the float32 draw, the scale
    # and the cast fuse, so a large bf16 leaf never exists in float32
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    # fan-in scaled normal by default; embed uses 1.0 stddev like most LMs.
    if spec.init_scale is not None:
        std = spec.init_scale
    elif spec.init == "embed":
        std = 0.02
    else:
        fan_in = spec.shape[0] if len(spec.shape) >= 2 else max(spec.size, 1)
        # stacked-layer params: fan-in excludes the leading "layers" axis
        if spec.axes and spec.axes[0] == "layers" and len(spec.shape) >= 3:
            fan_in = spec.shape[1]
        std = float(fan_in) ** -0.5
    out = std * jax.random.normal(key, spec.shape, jnp.float32)
    return out.astype(spec.dtype)


def init_params(rng: jax.Array, specs: PyTree) -> PyTree:
    """Materialize a param pytree from specs, keyed deterministically by path."""
    seed = int(jax.random.randint(rng, (), 0, 2**31 - 1))

    def one(path, spec: ParamSpec):
        h = int.from_bytes(
            hashlib.sha256(_path_str(path).encode()).digest()[:4], "little")
        key = jax.random.PRNGKey(np.uint32((seed + h) % (2**31)))
        return _init_one(spec, key)

    return jax.tree_util.tree_map_with_path(
        one, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def abstract_params(specs: PyTree) -> PyTree:
    return jax.tree_util.tree_map(
        lambda s: s.abstract(), specs,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def param_count(specs: PyTree) -> int:
    leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    return sum(l.size for l in leaves)


def param_bytes(specs: PyTree) -> int:
    leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    return sum(l.size * jnp.dtype(l.dtype).itemsize for l in leaves)


def map_axes(specs: PyTree, fn: Callable[[tuple[str | None, ...]], Any]) -> PyTree:
    """Map each ParamSpec's logical axes through ``fn`` (e.g. -> PartitionSpec)."""
    return jax.tree_util.tree_map(
        lambda s: fn(s.axes), specs,
        is_leaf=lambda x: isinstance(x, ParamSpec))
