"""RMSNorm over the last axis, x / sqrt(mean(x^2) + eps) * (1 + w).

Work from the shapes: square, sum, scale and weight of each element, and
x, w and the output each moved once.
"""
import jax.numpy as jnp
import numpy as np

VERSIONS = {"v1": "rmsnorm_unfused", "v2": "rmsnorm_fused"}


def shapes(cfg):
    d = cfg["deployment"]
    D = cfg["hidden_size"]
    return [((d["batch"] * d["seq_len"], D), d["dtype"]), ((D,), d["dtype"])]


def work(arg_shapes):
    (x_shape, x_dtype), (w_shape, w_dtype) = arg_shapes
    n = int(np.prod(x_shape))
    flops = 4 * n
    moved = (2 * n * jnp.dtype(x_dtype).itemsize
             + int(np.prod(w_shape)) * jnp.dtype(w_dtype).itemsize)
    return flops, moved
