"""Plain RMSNorm in float32: x / sqrt(mean(x^2) + eps) * (1 + w), with the
configuration's ``rms_norm_eps``."""
import functools

import jax
import jax.numpy as jnp

from bench.reference.fp8 import fp8


@functools.partial(jax.jit, static_argnames=("eps", "round_to"))
def _rmsnorm(x, w, eps, round_to=None):
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if round_to is not None:
        x, w = fp8(x), fp8(w)
    y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    y = y * (1.0 + w)
    return fp8(y) if round_to is not None else y


def inputs(key, shapes):
    """x standard normal; the weight w normal with deviation 0.5, so that
    the scale 1 + w spans about 0 to 2 and a dropped weight shows."""
    kx, kw = jax.random.split(key)
    x, w = shapes
    return (jax.random.normal(kx, x.shape, x.dtype),
            (0.5 * jax.random.normal(kw, w.shape, jnp.float32)
             ).astype(w.dtype))


def reference(args, cfg):
    return _rmsnorm(*args, eps=float(cfg["rms_norm_eps"]))


def control(args, cfg):
    """The reference with its inputs and output rounded to float8."""
    return _rmsnorm(*args, eps=float(cfg["rms_norm_eps"]), round_to="fp8")
