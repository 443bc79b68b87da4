"""Seeded random weights, made on the device in one jitted call.

The tree has the layout the serving program takes (one scanned run of
``n_layers`` dense GQA layers, weights (out, in) in bf16, LayerNorm
gamma/beta in f32) and is the only weight source of both the program and
the plain reference: the reference never reads anything the program made.
Scales: a linear's weight is N(0, 1/d_in); the embedding (and an untied
head) N(0, 1/d_model); LayerNorm gamma 1 + N(0, 0.1²), beta N(0, 0.1²), so
the norms' affine parameters matter to the result.
"""
from __future__ import annotations

import functools


def key_of(seed: int):
    """A PRNG key from a seed of any size (both 32-bit halves count)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def shapes(d) -> dict:
    """{path: (shape, kind)} of the weight tree for :class:`Dims` ``d``."""
    out = {"embed": ((d.V, d.D), "embed"),
           "final_norm.gamma": ((d.D,), "gamma"),
           "final_norm.beta": ((d.D,), "beta")}
    if not d.tied:
        out["lm_head"] = ((d.V, d.D), "embed")
    layer = {"ln1.gamma": ((d.D,), "gamma"), "ln1.beta": ((d.D,), "beta"),
             "ln2.gamma": ((d.D,), "gamma"), "ln2.beta": ((d.D,), "beta")}
    layer.update({name: ((o, i), "linear") for name, o, i in d.linears()})
    for k, (s, kind) in layer.items():
        out["stack.0.u0." + k] = ((d.L,) + s, kind)
    return out


def _leaf(key, shape, kind):
    import jax
    import jax.numpy as jnp

    if kind == "gamma":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if kind == "beta":
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    fan_in = shape[-1]
    return (jax.random.normal(key, shape, jnp.bfloat16)
            * jnp.bfloat16(fan_in ** -0.5))


def _nest(flat: dict):
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parts, last = path.split(".")
        for p in parts:
            node = node.setdefault(p, {})
        node[last] = v
    tree["stack"] = [tree["stack"]["0"]]
    return tree


@functools.lru_cache(maxsize=None)
def _maker(items: tuple):
    import jax

    def make(key):
        keys = jax.random.split(key, len(items))
        return _nest({p: _leaf(k, s, kind)
                      for k, (p, (s, kind)) in zip(keys, items)})
    return jax.jit(make)


def make(d, seed: int):
    """The weight tree for :class:`Dims` ``d`` from ``seed``, on the
    default device."""
    items = tuple(sorted(shapes(d).items()))
    return _maker(items)(key_of(seed))
