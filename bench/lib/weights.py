"""Random weights for a model, drawn on the device in one jitted call.

The draw follows the initializers the model declares for each leaf
(``ParamSpec.init``): zeros, ones, or a normal whose standard deviation
is the leaf's ``init_scale``, 0.02 for embeddings, and otherwise
``fan_in ** -0.5`` with ``fan_in`` the leaf's first axis (its second for
stacked layers).  That is the distribution of the program's own
``Model.init``; only the keys differ, because one call draws every leaf
from ``fold_in`` of a key made from the seed, in the dtype it is served
in, with no float32 copy of a large leaf.
"""
from __future__ import annotations

import numpy as np

from bench.lib.traffic import split_seed


def _std(spec) -> float:
    if spec.init_scale is not None:
        return float(spec.init_scale)
    if spec.init == "embed":
        return 0.02
    size = int(np.prod(spec.shape)) if spec.shape else 1
    fan_in = spec.shape[0] if len(spec.shape) >= 2 else max(size, 1)
    if spec.axes and spec.axes[0] == "layers" and len(spec.shape) >= 3:
        fan_in = spec.shape[1]
    return float(fan_in) ** -0.5


def draw(model, seed: int, stream: int):
    """The parameter tree of ``model`` for (``seed``, ``stream``)."""
    import jax
    import jax.numpy as jnp

    from repro.models.params import ParamSpec
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        model.param_specs(), is_leaf=lambda x: isinstance(x, ParamSpec))
    leaves = [spec for _, spec in flat]
    words = split_seed(seed)
    if len(words) > 2:
        raise ValueError("seed above 2**64")

    def make(words):
        key = jax.random.PRNGKey(0)
        for i in range(words.shape[0]):
            key = jax.random.fold_in(key, words[i])
        keys = jax.random.split(key, len(leaves))
        out = []
        for spec, k in zip(leaves, keys):
            if spec.init == "zeros":
                out.append(jnp.zeros(spec.shape, spec.dtype))
            elif spec.init == "ones":
                out.append(jnp.ones(spec.shape, spec.dtype))
            else:
                out.append((_std(spec) * jax.random.normal(
                    k, spec.shape, jnp.float32)).astype(spec.dtype))
        return out

    arr = np.array([stream] + (words + [0])[:2], np.uint32)
    out = jax.jit(make)(arr)
    jax.block_until_ready(out)
    return jax.tree_util.tree_unflatten(treedef, out)
