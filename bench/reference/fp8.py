"""Rounding to the precision below bf16 that a later change could be
tempted to take: float8 e4m3 (4 exponent and 3 mantissa bits, against
bf16's 8 and 7).  `reduce_precision` and not a round trip through the
float8 type: XLA may drop a convert pair as excess precision, and on the
TPU it does."""
import jax


def fp8(x):
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
