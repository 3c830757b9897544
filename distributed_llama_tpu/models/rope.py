"""Rotary position embeddings: llama (interleaved), falcon (neox halves),
llama-3.1 (frequency scaling for 128K contexts).

Reference behaviors: LlamaRopeCommand (src/commands.cpp:140-179) rotates
interleaved pairs (2j, 2j+1) with freq = theta^(-2j/head_size);
FalconRopeCommand (src/commands.cpp:229-257) rotates pairs (j, j+half);
Llama3_1RopeCommand (src/commands.cpp:181-227) adds wavelength-dependent
frequency scaling.

TPU-first design: cos/sin tables are precomputed once on host as [seq_len,
head_size/2] arrays and gathered by position inside the jitted step —
matching the reference's precomputed cache idea (commands.cpp:147-157) but
vectorized over all heads/positions at once.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llama_tpu.formats.model_file import RopeType
from distributed_llama_tpu.models.config import LlamaConfig


def _llama3_scale_freqs(freqs: np.ndarray, cfg: LlamaConfig) -> np.ndarray:
    """Llama 3.1 NTK-by-parts frequency scaling (the *correct* form, as in the
    original Meta/HF implementation; the reference's value-space variant is
    available via cfg.rope_llama3_reference_quirk)."""
    factor = cfg.rope_scaling_factor
    low = cfg.rope_scaling_low_freq_factor
    high = cfg.rope_scaling_high_freq_factor
    orig = cfg.rope_scaling_orig_max_seq_len
    if factor == 0 or orig == 0:
        return freqs
    wavelen = 2.0 * math.pi / freqs
    low_wavelen = orig / low
    high_wavelen = orig / high
    scaled = np.where(wavelen > low_wavelen, freqs / factor, freqs)
    smooth = (orig / wavelen - low) / (high - low)
    smoothed = (1 - smooth) * freqs / factor + smooth * freqs
    mid = (wavelen <= low_wavelen) & (wavelen >= high_wavelen)
    return np.where(mid, smoothed, scaled).astype(freqs.dtype)


def build_rope_table(cfg: LlamaConfig) -> np.ndarray:
    """Precompute [seq_len, rope_dim/2, 2] (cos, sin) in float32; ``rope_dim``
    is the head size, but for a latent layer's rotated slice."""
    half = cfg.rope_dim // 2
    j = np.arange(half, dtype=np.float64)
    freqs = 1.0 / (cfg.rope_theta ** (2.0 * j / cfg.rope_dim))
    if cfg.rope_type == RopeType.LLAMA3_1 and not cfg.rope_llama3_reference_quirk:
        freqs = _llama3_scale_freqs(freqs.astype(np.float64), cfg)
    pos = np.arange(cfg.seq_len, dtype=np.float64)
    angles = pos[:, None] * freqs[None, :]
    table = np.stack([np.cos(angles), np.sin(angles)], axis=-1)
    return table.astype(np.float32)


def _reference_llama3_value_scale(v: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """The reference's Llama3_1RopeCommand::scale applied to rotated values
    (reference: src/commands.cpp:193-205, 224-225). Kept only for bit-parity
    experiments against the C++ runtime."""
    factor = cfg.rope_scaling_factor
    low = cfg.rope_scaling_low_freq_factor
    high = cfg.rope_scaling_high_freq_factor
    orig = cfg.rope_scaling_orig_max_seq_len
    wave_len = 2.0 * math.pi * v
    low_wavelen = orig / low
    high_wavelen = orig / high
    smooth = (orig / wave_len - low) / (high - low)
    smoothed = (1 - smooth) * v / factor + smooth * v
    return jnp.where(
        wave_len < high_wavelen, v, jnp.where(wave_len > low_wavelen, v / factor, smoothed)
    )


def apply_rope_interleaved(
    x: jax.Array, table_slice: jax.Array, cfg: LlamaConfig
) -> jax.Array:
    """Rotate interleaved pairs. ``x``: [T, n_heads, head_size];
    ``table_slice``: [T, head_size/2, 2] rows already gathered by position."""
    shape = x.shape
    xp = x.reshape(*shape[:-1], shape[-1] // 2, 2)
    cos = table_slice[:, None, :, 0]
    sin = table_slice[:, None, :, 1]
    v0 = xp[..., 0]
    v1 = xp[..., 1]
    r0 = v0 * cos - v1 * sin
    r1 = v0 * sin + v1 * cos
    if cfg.rope_type == RopeType.LLAMA3_1 and cfg.rope_llama3_reference_quirk:
        r0 = _reference_llama3_value_scale(r0, cfg)
        r1 = _reference_llama3_value_scale(r1, cfg)
    return jnp.stack([r0, r1], axis=-1).reshape(shape)


def apply_rope_neox(x: jax.Array, table_slice: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """Falcon/neox-style rotation of pairs (j, j+half). Same table (the
    frequency for pair j is theta^(-2j/head_size) in both layouts). ``x`` is
    as wide as the table's pairs say (a latent layer's rotated slice)."""
    half = x.shape[-1] // 2
    v0 = x[..., :half]
    v1 = x[..., half:]
    cos = table_slice[:, None, :, 0]
    sin = table_slice[:, None, :, 1]
    r0 = v0 * cos - v1 * sin
    r1 = v0 * sin + v1 * cos
    return jnp.concatenate([r0, r1], axis=-1)


def apply_rope(x: jax.Array, table_slice: jax.Array, cfg: LlamaConfig) -> jax.Array:
    if cfg.rope_type == RopeType.FALCON:
        return apply_rope_neox(x, table_slice, cfg)
    return apply_rope_interleaved(x, table_slice, cfg)
