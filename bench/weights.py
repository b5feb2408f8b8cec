"""Random weights from the seed, built on the device in one jitted call.

The tree has the shapes and dtypes the program serves (matrices in the
serving dtype, norm scales in float32), taken with ``jax.eval_shape`` so
nothing is drawn twice.  Values come from a counter-based integer hash of
each element's index and a per-leaf key, which writes the ~8 GB of a 4B
model at memory speed instead of running a cryptographic generator.
Every matrix, the embedding table among them, is uniform with the
configuration's published ``initializer_range`` as its standard
deviation; norm scales are 1, and an architecture module's
``leaf_rules`` may set further leaves by name to ones or zeros.  (A table
far wider than the layers' outputs would make every position predict its
own input token, and no precision would ever change a greedy token.)
The reference reads these same arrays.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# leaf name -> "ones" or "zeros"; every other leaf is drawn
LEAF_RULES = {"scale": "ones", "q_norm": "ones", "k_norm": "ones"}
FILLS = {"ones": jnp.ones, "zeros": jnp.zeros}
_MASK32 = (1 << 32) - 1


def _splitmix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & ((1 << 64) - 1)
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & ((1 << 64) - 1)
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & ((1 << 64) - 1)
    return x ^ (x >> 31)


def leaf_keys(seed: int, index: int) -> tuple:
    """Two 32-bit keys for leaf ``index`` under ``seed`` (any integer)."""
    h = _splitmix64((int(seed) & ((1 << 64) - 1)) ^ _splitmix64(index))
    return h & _MASK32, (h >> 32) & _MASK32


def _fmix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def uniform_pm1(shape, k1, k2):
    """Values in [-1, 1) from the hash of each element's flat index."""
    n = math.prod(shape)
    if n >= 1 << 32:
        raise ValueError(f"leaf of {n} elements exceeds the 32-bit counter")
    idx = jax.lax.iota(jnp.uint32, n).reshape(shape)
    x = _fmix32((idx * jnp.uint32(0x9E3779B9)) ^ k1)
    x = _fmix32(x + k2)
    u = (x >> 8).astype(jnp.float32) * np.float32(2.0 ** -24)
    return 2.0 * u - 1.0


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def build(cfg, seed: int, std: float, arch):
    """The serving parameter tree of ``cfg`` drawn from ``seed``, matrices
    with standard deviation ``std``; ``LEAF_RULES`` and then the
    architecture module's ``leaf_rules``, if it has any, fill leaves by
    name."""
    from repro.models import transformer as tfm

    rules = {**LEAF_RULES, **getattr(arch, "leaf_rules", {})}
    shapes = jax.eval_shape(
        lambda: tfm.init_params_serving(jax.random.PRNGKey(0), cfg))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = np.asarray([leaf_keys(seed, i) for i in range(len(flat))],
                      np.uint32)

    def make(keys):
        out = []
        for i, (path, sd) in enumerate(flat):
            fill = rules.get(_leaf_name(path))
            if fill is not None:
                out.append(FILLS[fill](sd.shape, sd.dtype))
                continue
            u = uniform_pm1(sd.shape, keys[i, 0], keys[i, 1])
            out.append((u * np.float32(std * math.sqrt(3.0))).astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    params = jax.jit(make)(jnp.asarray(keys))
    return jax.block_until_ready(params)


def param_count(params) -> tuple:
    leaves = jax.tree.leaves(params)
    return (sum(x.size for x in leaves),
            sum(x.size * x.dtype.itemsize for x in leaves))
