"""What every family's plain reference is made of: the Q40 block format
dequantized exactly, a matmul at ``highest`` precision (on a TPU a float32
product is otherwise computed in bfloat16 passes) and the RMS norm, in
straightforward float32 ``jax.numpy``. No kernel, no cache, nothing of the
program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-5
HI = jax.lax.Precision.HIGHEST


def dequant(raw: jax.Array) -> jax.Array:
    """uint8 [d_out, n_blocks, 18] -> float32 [d_out, n_blocks * 32]. A block
    is an f16 scale and 16 bytes; byte j holds value j in its low nibble and
    value j + 16 in its high nibble, both offset by 8."""
    lo16 = raw[..., 0].astype(jnp.uint16) | (raw[..., 1].astype(jnp.uint16) << 8)
    scale = jax.lax.bitcast_convert_type(lo16, jnp.float16).astype(jnp.float32)
    qs = raw[..., 2:]
    lo = (qs & 0xF).astype(jnp.int32) - 8
    hi = (qs >> 4).astype(jnp.int32) - 8
    vals = jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32) * scale[..., None]
    return vals.reshape(raw.shape[0], -1)


def rmsnorm(x, w):
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)


def matmul(x, raw):
    """y = x @ W.T for a Q40 matrix W [d_out, d_in]."""
    return jnp.einsum("...i,oi->...o", x, dequant(raw), precision=HI)
