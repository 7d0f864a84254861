"""Plain Mamba-2 SSD recurrence in float32, one step at a time.

Per head h (group h // (H / G)) and step t, from a zero state:

    state_t = exp(A_h dt_t) state_{t-1} + dt_t x_t B_t^T
    y_t     = state_t C_t

Arguments in the model's layout: x [B,S,H,P], dt [B,S,H], A [H],
B and C [B,S,G,N].  The read-out runs at HIGHEST precision.
"""
import functools

import jax
import jax.numpy as jnp

from bench.reference.fp8 import fp8

HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames="round_to")
def _ssd(x, dt, A, Bm, Cm, round_to=None):
    x, dt, A, Bm, Cm = (t.astype(jnp.float32) for t in (x, dt, A, Bm, Cm))
    if round_to is not None:
        x, Bm, Cm = fp8(x), fp8(Bm), fp8(Cm)
    Bz, S, H, P = x.shape
    hg = H // Bm.shape[2]
    Bm, Cm = jnp.repeat(Bm, hg, axis=2), jnp.repeat(Cm, hg, axis=2)

    def step(state, t):
        xt, dtt, bt, ct = t
        decay = jnp.exp(dtt * A)                              # [B,H]
        state = (state * decay[..., None, None]
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        y = jnp.einsum("bhpn,bhn->bhp", state, ct, precision=HIGHEST)
        return state, y

    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, Bm, Cm))
    state0 = jnp.zeros((Bz, H, P, Bm.shape[-1]), jnp.float32)
    _, ys = jax.lax.scan(step, state0, seq)                  # [S,B,H,P]
    y = jnp.moveaxis(ys, 0, 1)
    return fp8(y) if round_to is not None else y


def inputs(key, shapes):
    """x standard normal; dt = softplus of a standard normal; A =
    -exp(z / 2) for a standard normal z, so every head decays; B and C
    normal with deviation 0.5."""
    keys = jax.random.split(key, len(shapes))
    x, dt, a, b, c = (jax.random.normal(k, s.shape, jnp.float32)
                      for k, s in zip(keys, shapes))
    return tuple(t.astype(s.dtype) for t, s in zip(
        (x, jax.nn.softplus(dt), -jnp.exp(a * 0.5), b * 0.5, c * 0.5),
        shapes))


def reference(args, cfg):
    return _ssd(*args)


def control(args, cfg):
    """The reference with x, B, C and the output rounded to float8 (dt and
    A are float32 in the program too)."""
    return _ssd(*args, round_to="fp8")
