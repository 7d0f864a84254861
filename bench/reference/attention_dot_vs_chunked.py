"""Plain causal grouped-query attention in float32, in blocks of queries.

Head h reads key/value head h // (H / K).  Scores are scaled by
1 / sqrt(hd); query i sees keys 0..i.  Matrix products run at HIGHEST
precision, so the TPU does not round their inputs to bf16.
"""
import functools

import jax
import jax.numpy as jnp

from bench.reference.fp8 import fp8

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 512


@functools.partial(jax.jit, static_argnames="round_to")
def _attention(q, k, v, round_to=None):
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    if round_to is not None:
        q, k, v = fp8(q), fp8(k), fp8(v)
    B, S, H, hd = q.shape
    G = H // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    blk = min(BLOCK, S)
    if S % blk:
        raise ValueError(f"sequence {S} is not a multiple of {blk}")
    k_pos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HIGHEST)
        s = s / jnp.sqrt(jnp.float32(hd))
        q_pos = i * blk + jnp.arange(blk)
        s = jnp.where(k_pos[None, :] <= q_pos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(S // blk))      # [nb, B, blk, H, hd]
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, H, hd)
    return fp8(out) if round_to is not None else out


def inputs(key, shapes):
    """q, k and v, standard normal in their dtypes."""
    keys = jax.random.split(key, len(shapes))
    return tuple(jax.random.normal(k, s.shape, s.dtype)
                 for k, s in zip(keys, shapes))


def reference(args, cfg):
    return _attention(*args)


def control(args, cfg):
    """The reference with its inputs and output rounded to float8."""
    return _attention(*args, round_to="fp8")
