"""Mamba-2 SSD chunked-scan Pallas TPU kernel [arXiv:2405.21060].

Layout (head-major): x [B, H, S, P]; dt [B, H, S]; A [H];
B_in/C_in [B, G, S, N]; outputs y [B, H, S, P], final state [B, H, P, N].

Grid (B, H, n_chunks) — chunks innermost; the fp32 state [P, N] lives in
VMEM scratch and is carried across chunk steps (sequential TPU grid).  Per
chunk the kernel evaluates the SSD block decomposition:

    intra-chunk:  y += ((C B^T) .* decay(i,j) .* dt_j, masked i>=j) @ x
    inter-chunk:  y += exp(cum_i) * (C @ state^T)
    state        = exp(total) * state + x^T @ (B .* w_j),  w_j = exp(total-cum_j) dt_j

All dots are MXU-shaped ([Q,N]x[N,Q], [Q,Q]x[Q,P], [P,Q]x[Q,N]) with
Q = chunk (default 256), N = d_state (128), P = head_dim — every matmul
dimension a multiple of 128 at the assigned configs (chunk 256, N 128,
P 64/128; P=64 pads to sublane tiles, still MXU-friendly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 256


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, state_ref, *,
            chunk: int, n_chunks: int, seq_valid: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    hp = jax.lax.Precision.HIGHEST
    x = x_ref[0, 0].astype(jnp.float32)                     # [Q, P]
    dt = dt_ref[0, 0].astype(jnp.float32)                   # [Q, 1]
    a = a_ref[pl.program_id(1)]                             # scalar (<0), SMEM
    Bm = b_ref[0, 0].astype(jnp.float32)                    # [Q, N]
    Cm = c_ref[0, 0].astype(jnp.float32)                    # [Q, N]

    # zero out padded tail rows (dt=0 is an exact no-op)
    t_pos = ic * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    dt = jnp.where(t_pos < seq_valid, dt, 0.0)

    # Mosaic has no cumsum: the inclusive prefix sum is a lower-triangular
    # matmul, taken once as a column and once (transposed lhs) as a row so
    # every vector stays 2-D
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = ii >= jj
    da = dt * a                                             # [Q, 1] <= 0
    cum = jax.lax.dot(causal.astype(jnp.float32), da, precision=hp,
                      preferred_element_type=jnp.float32)   # [Q, 1]
    cum_row = jax.lax.dot_general(
        da, (ii <= jj).astype(jnp.float32), (((0,), (0,)), ((), ())),
        precision=hp, preferred_element_type=jnp.float32)   # [1, Q]
    total = jnp.sum(da, axis=0, keepdims=True)              # [1, 1]

    # ---- intra-chunk --------------------------------------------------
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Qi,Qj]
    att = jnp.where(causal, cb * jnp.exp(cum - cum_row), 0.0)
    # dt_j scales column j of att, i.e. row j of x
    y = jax.lax.dot(att, x * dt, preferred_element_type=jnp.float32)  # [Q,P]

    # ---- inter-chunk ---------------------------------------------------
    state = state_ref[...]                                  # [P, N]
    ch = jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q,P]
    y = y + jnp.exp(cum) * ch

    # ---- state update ---------------------------------------------------
    w = jnp.exp(total - cum) * dt                           # [Q, 1]
    s_new = jax.lax.dot_general(x, Bm * w, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [P,N]
    state_ref[...] = state * jnp.exp(total) + s_new

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        st_ref[0, 0] = state_ref[...].astype(st_ref.dtype)


def ssd_scan_hmajor(x, dt, A, B_in, C_in, *, chunk=DEFAULT_CHUNK,
                    interpret=False):
    """x [B,H,S,P]; dt [B,H,S]; A [H]; B_in/C_in [B,G,S,N].

    Returns (y [B,H,S,P], state [B,H,P,N] fp32)."""
    B, H, S, P = x.shape
    G, N = B_in.shape[1], B_in.shape[3]
    assert H % G == 0
    hg = H // G
    chunk = min(chunk, _round_up(S, 8))
    S_p = _round_up(S, chunk)
    if S_p != S:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, S_p - S), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, S_p - S)))
        B_in = jnp.pad(B_in, ((0, 0), (0, 0), (0, S_p - S), (0, 0)))
        C_in = jnp.pad(C_in, ((0, 0), (0, 0), (0, S_p - S), (0, 0)))
    n_chunks = S_p // chunk

    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks,
                               seq_valid=S)
    y, state = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h // hg, c, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h // hg, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S_p, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, dt[..., None], A.astype(jnp.float32), B_in, C_in)
    return y[:, :, :S], state


def _round_up(x, m):
    return ((x + m - 1) // m) * m
