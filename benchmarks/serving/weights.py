"""The benchmark's own random weights, made from ``--seed``.

Every leaf is a pure function of (seed, the leaf's path in the served
parameter tree, the layer index), so the served path can make the whole
tree in one program while the plain reference regenerates one layer at a
time from the same seed and never touches what the program made.  Keys
enter the programs as arguments, so one compiled program serves every
seed.

Leaf kinds, by the last key of the path; each recipe maps a standard
normal draw ``z`` of the leaf's shape:

  ``w``      a linear weight [K, N]: z / sqrt(K), so a layer keeps the
             scale of its input;
  ``b``      a bias: z * 0.1, small beside the product it shifts;
  ``scale``  a norm gain: 1 + 0.1 * z, near the identity;
  ``embed``  the token table (the head where it is tied): z * 0.02;
  ``experts_wg``, ``experts_wu``, ``experts_wd``  an expert bank
             [E, K, N]: as ``w`` over its K axis, each expert a linear;
  ``lm_head`` an untied head, and ``pos_embed`` a position table: as
             ``embed``, at the token table's scale;
  ``bias``, ``conv_b``  a norm's or a convolution's bias: as ``b``;
  ``d_skip`` a recurrent mixer's skip gain: as ``scale``, near the
             identity;
  ``conv_w`` a depthwise convolution [channels, width]: z / sqrt(width),
             so a channel keeps its scale across the window;
  ``a_log``  a selective state space's decay, A = -exp(a_log), over
             [channels, state]: exp(a_log) runs log-evenly from 2**-8 up
             to 1 along the state axis, times exp(0.1 * z).  At a step
             dt near 0.7 (the softplus of a ``b`` bias) state index 0 of
             every channel, in every layer, keeps exp(-16 dt 2**-8
             e**(0.1 z)) of itself across a 16-token chunk: above 0.9
             for z < 8, and above a half for any dt up to 10.  The output
             then depends on a state carried from chunk to chunk, so a
             state dropped between chunks shows.  The last index (A near
             -1) forgets within a few tokens, as the published
             initialisation's do.

A reference may bring recipes of its own (``make_tree``'s ``recipes``)
for kinds this table lacks; its table is looked up before this one.
"""
from __future__ import annotations

import zlib
from collections.abc import Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

STACKED = "['blocks']"     # leaves under this prefix carry a layer axis
A_LEAST = 2.0 ** -8        # the slowest decay of ``a_log``, per unit dt

Recipes = Mapping[str, Callable[[jax.Array], jax.Array]]


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


def _linear(z):
    return z / np.sqrt(z.shape[-2])


def _bias(z):
    return z * 0.1


def _gain(z):
    return 1.0 + 0.1 * z


def _table(z):
    return z * 0.02


def _a_log(z):
    base = jnp.linspace(np.log(A_LEAST), 0.0, z.shape[-1])
    return base + 0.1 * z


RECIPES: dict[str, Callable[[jax.Array], jax.Array]] = {
    "w": _linear, "b": _bias, "scale": _gain, "embed": _table,
    "experts_wg": _linear, "experts_wu": _linear, "experts_wd": _linear,
    "lm_head": _table, "pos_embed": _table,
    "bias": _bias, "conv_b": _bias, "d_skip": _gain,
    "conv_w": lambda z: z / np.sqrt(z.shape[-1]),
    "a_log": _a_log,
}


def leaf(key: jax.Array, kind: str, shape: tuple[int, ...],
         recipes: Recipes | None = None) -> jax.Array:
    """One unstacked leaf of ``kind`` in float32; ``recipes`` (a
    reference's own) are looked up before the table above."""
    recipe = (recipes or {}).get(kind) or RECIPES.get(kind)
    if recipe is None:
        raise ValueError(f"no weight recipe for a leaf named {kind!r}")
    return recipe(jax.random.normal(key, shape, jnp.float32))


def layer_leaf(key: jax.Array, kind: str, layer, shape: tuple[int, ...],
               recipes: Recipes | None = None) -> jax.Array:
    """Layer ``layer`` (may be traced) of a stacked leaf whose base key
    is ``key``."""
    return leaf(jax.random.fold_in(key, layer), kind, shape, recipes)


def tree_keys(seed: int, shapes) -> dict[str, jax.Array]:
    """Base key of every leaf of a tree of ``ShapeDtypeStruct``."""
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    return {jax.tree_util.keystr(kp): path_key(seed, jax.tree_util.keystr(kp))
            for kp, _ in leaves}


def make_tree(keys: dict[str, jax.Array], shapes,
              recipes: Recipes | None = None):
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
                layer_leaf(k, kind, i, shape, recipes))(
                    jnp.arange(sds.shape[0])))
        else:
            out.append(leaf(keys[path], kind, tuple(sds.shape), recipes))
    return jax.tree_util.tree_unflatten(treedef, out)
