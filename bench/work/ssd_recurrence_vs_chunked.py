"""Mamba-2 SSD (state-space duality) scan, per head h and step t:

    state_t = exp(A_h dt_t) state_{t-1} + dt_t x_t B_t^T      [P, N]
    y_t     = state_t C_t                                      [P]

Work from the shapes, as the recurrence does it: the state update (decay,
outer product, sum: 3 P N per head and step) and the read-out (2 P N), and
x, dt, A, B, C and y each moved once.
"""
import jax.numpy as jnp
import numpy as np

VERSIONS = {"v1": "ssd_recurrence", "v2": "ssd_chunked"}


def shapes(cfg):
    """Arguments (x, dt, A, B, C) in the model's [B, S, ...] layout."""
    d = cfg["deployment"]
    B, S = d["batch"], d["seq_len"]
    P, N, G = cfg["headdim"], cfg["d_state"], cfg["ngroups"]
    H = cfg["expand"] * cfg["d_model"] // P
    dt = d["dtype"]
    return [((B, S, H, P), dt), ((B, S, H), "float32"), ((H,), "float32"),
            ((B, S, G, N), dt), ((B, S, G, N), dt)]


def work(arg_shapes):
    (x_s, x_t), (dt_s, dt_t), (a_s, a_t), (b_s, b_t), (c_s, c_t) = arg_shapes
    B, S, H, P = x_s
    N = b_s[3]
    flops = 5 * B * S * H * P * N
    size = lambda s, t: int(np.prod(s)) * jnp.dtype(t).itemsize
    moved = (2 * size(x_s, x_t) + size(dt_s, dt_t) + size(a_s, a_t)
             + size(b_s, b_t) + size(c_s, c_t))
    return flops, moved
