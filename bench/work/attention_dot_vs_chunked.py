"""Causal grouped-query attention, softmax(q k^T / sqrt(hd)) v.

Work of the function both versions compute, from its shapes: the two
matrix products over the causal pairs only (query i sees keys 0..i), and
q, k, v and o each moved once.  Softmax and masking are not counted.
"""
import jax.numpy as jnp
import numpy as np

VERSIONS = {"v1": "attention_dot", "v2": "attention_chunked"}


def shapes(cfg):
    """Argument shapes (q, k, v) the configuration gives the duet."""
    d = cfg["deployment"]
    B, S = d["batch"], d["seq_len"]
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // H
    return [((B, S, H, hd), d["dtype"]), ((B, S, K, hd), d["dtype"]),
            ((B, S, K, hd), d["dtype"])]


def work(arg_shapes):
    """(floating-point operations, bytes) of one call."""
    (q_shape, q_dtype), (k_shape, k_dtype), _ = arg_shapes
    B, S, H, hd = q_shape
    K = k_shape[2]
    pairs = S * (S + 1) // 2
    flops = 2 * 2 * B * H * pairs * hd
    moved = (2 * B * S * H * hd * jnp.dtype(q_dtype).itemsize
             + 2 * B * S * K * hd * jnp.dtype(k_dtype).itemsize)
    return flops, moved
