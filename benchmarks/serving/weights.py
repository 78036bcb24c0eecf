"""The benchmark's own random weights, made from ``--seed``.

Every leaf is a pure function of (seed, the leaf's path in the served
parameter tree, the layer index), so the served path can make the whole
tree in one program while the plain reference regenerates one layer at a
time from the same seed and never touches what the program made.  Keys
enter the programs as arguments, so one compiled program serves every
seed.

Leaf kinds, by the last key of the path:

  ``w``      a linear weight [K, N]: normal / sqrt(K);
  ``b``      a bias: normal * 0.1;
  ``scale``  a norm gain: 1 + 0.1 * normal;
  ``embed``  the token table (tied head): normal * 0.02.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

STACKED = "['blocks']"     # leaves under this prefix carry a layer axis


def seed32(seed: int) -> int:
    """A 31-bit key for ``jax.random`` from any whole-number seed."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0] >> 1)


def path_key(seed: int, path: str) -> jax.Array:
    """The base key of one leaf: the seed folded with the path's CRC."""
    return jax.random.fold_in(jax.random.PRNGKey(seed32(seed)),
                              zlib.crc32(path.encode()))


def kind_of(path: str) -> str:
    """``"['blocks'][0]['attn']['wq']['w']"`` -> ``"w"``."""
    return path.rsplit("'", 2)[-2]


def leaf(key: jax.Array, kind: str, shape: tuple[int, ...]) -> jax.Array:
    """One unstacked leaf of ``kind`` in float32."""
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "w":
        return z / np.sqrt(shape[-2])
    if kind == "b":
        return z * 0.1
    if kind == "scale":
        return 1.0 + 0.1 * z
    if kind == "embed":
        return z * 0.02
    raise ValueError(f"no weight recipe for a leaf named {kind!r}")


def layer_leaf(key: jax.Array, kind: str, layer, shape: tuple[int, ...]
               ) -> jax.Array:
    """Layer ``layer`` (may be traced) of a stacked leaf whose base key
    is ``key``."""
    return leaf(jax.random.fold_in(key, layer), kind, shape)


def tree_keys(seed: int, shapes) -> dict[str, jax.Array]:
    """Base key of every leaf of a tree of ``ShapeDtypeStruct``."""
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    return {jax.tree_util.keystr(kp): path_key(seed, jax.tree_util.keystr(kp))
            for kp, _ in leaves}


def make_tree(keys: dict[str, jax.Array], shapes):
    """Float32 leaves for ``shapes``, each from its base key in ``keys``.
    Stacked leaves are made layer by layer (``layer_leaf``), so that one
    layer can be regenerated alone."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for kp, sds in leaves:
        path = jax.tree_util.keystr(kp)
        kind = kind_of(path)
        if path.startswith(STACKED):
            shape = tuple(sds.shape[1:])
            out.append(jax.vmap(
                lambda i, k=keys[path], kind=kind, shape=shape:
                layer_leaf(k, kind, i, shape))(jnp.arange(sds.shape[0])))
        else:
            out.append(leaf(keys[path], kind, tuple(sds.shape)))
    return jax.tree_util.tree_unflatten(treedef, out)
