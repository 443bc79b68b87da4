"""Plain reference of the configurations' dense GQA decoder.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, one
sequence at a time, no cache, no kernels, no batching, no quantization;
it imports nothing of the program.  Pre-LayerNorm blocks, rotate-half RoPE
over the whole head, causal softmax attention with grouped K/V heads, a
two-matrix MLP, a final LayerNorm and the output head (the embedding when
tied).

:func:`gaps` runs the forward pass over a prompt and the tokens the
program served for it and returns, for each served token, by how much its
logit lies below the reference's best logit at that position — 0 where the
program chose the reference's own argmax.
"""
from __future__ import annotations

import functools

import numpy as np

ROW_BLOCK = 256          # rows of the output head computed at once


def _ln(x, p, eps):
    import jax.numpy as jnp

    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["gamma"] + p["beta"]


def _rope(x, theta):
    import jax.numpy as jnp

    S, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(d, x, p):
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    S = x.shape[0]
    h = _ln(x, p["ln1"], d.eps)
    m = p["mix"]
    q = _rope((h @ m["wq"].T).reshape(S, d.H, d.hd), d.theta)
    k = _rope((h @ m["wk"].T).reshape(S, d.Hkv, d.hd), d.theta)
    v = (h @ m["wv"].T).reshape(S, d.Hkv, d.hd)
    k = jnp.repeat(k, d.H // d.Hkv, axis=1)
    v = jnp.repeat(v, d.H // d.Hkv, axis=1)
    s = jnp.einsum("shd,thd->hst", q, k) / np.sqrt(d.hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hst,thd->shd", a, v).reshape(S, d.H * d.hd)
    x = x + o @ m["wo"].T
    h = _ln(x, p["ln2"], d.eps)
    act = {"relu": jax.nn.relu,
           "gelu": functools.partial(jax.nn.gelu, approximate=True)}[d.act]
    return x + act(h @ p["mlp"]["w1"].T) @ p["mlp"]["w2"].T


@functools.lru_cache(maxsize=None)
def _program(d, S: int):
    """(weights, tokens (S,)) → (best (S,), chosen (S,)): at each position
    the reference's largest next-token logit and its logit of the next
    token in ``tokens``."""
    import jax
    import jax.numpy as jnp

    def run(w, tokens):
        with jax.default_matmul_precision("highest"):
            f32 = jnp.float32
            x = w["embed"][tokens].astype(f32)
            x, _ = jax.lax.scan(lambda c, p: (_layer(d, c, p), None), x,
                                w["stack"][0]["u0"])
            fn = jax.tree.map(lambda a: a.astype(f32), w["final_norm"])
            x = _ln(x, fn, d.eps)
            head = (w["embed"] if d.tied else w["lm_head"]).astype(f32)
            nxt = jnp.concatenate([tokens[1:], tokens[:1]])

            def block(args):
                xb, tb = args
                lg = xb @ head.T
                return lg.max(-1), jnp.take_along_axis(lg, tb[:, None],
                                                       -1)[:, 0]
            nb = S // ROW_BLOCK
            best, chosen = jax.lax.map(block, (
                x.reshape(nb, ROW_BLOCK, -1), nxt.reshape(nb, ROW_BLOCK)))
            return best.reshape(S), chosen.reshape(S)
    return jax.jit(run)


def padded_len(n: int) -> int:
    return -(-n // ROW_BLOCK) * ROW_BLOCK


def gaps(d, weights, prompt, served, S: int) -> np.ndarray:
    """Gaps (len(served),) of the served tokens below the reference's best
    logit, over ``prompt + served`` padded to ``S`` (a multiple of
    ``ROW_BLOCK``; one compiled program per S)."""
    import jax.numpy as jnp

    seq = list(prompt) + list(served)
    toks = np.zeros((S,), np.int32)
    toks[:len(seq)] = seq
    best, chosen = _program(d, S)(weights, jnp.asarray(toks))
    best, chosen = np.asarray(best), np.asarray(chosen)
    rows = slice(len(prompt) - 1, len(seq) - 1)
    return best[rows] - chosen[rows]
