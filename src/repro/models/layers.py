"""Shared numeric primitives + the forward-pass context object."""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.dist.sharding import DEFAULT_RULES, ShardingRules, logical_to_spec


@dataclass(frozen=True)
class Ctx:
    """Threading object for the forward pass.

    ``mesh=None`` (CPU smoke tests) turns sharding constraints into no-ops.
    ``use_pallas`` switches attention / RG-LRU / WKV to the Pallas TPU
    kernels (validated on CPU via interpret mode; the dry-run uses jnp).
    """

    mesh: Optional[Mesh] = None
    rules: ShardingRules = DEFAULT_RULES
    use_pallas: bool = False
    attn_q_block: int = 1024     # flash-style kv-chunked attention block sizes
    attn_kv_block: int = 1024
    rwkv_chunk: int = 32
    dtype: jnp.dtype = jnp.bfloat16
    # Unroll the layer scan: used by the roofline analysis variants, where
    # XLA's cost model needs loop-free HLO to count FLOPs exactly.
    scan_unroll: bool = False
    # Re-constrain scanned weight slices inside the loop body (perf A/B knob;
    # measured neutral on CPU-XLA — see models/model.py comment).
    constrain_scan_weights: bool = False

    def constrain(self, x: jax.Array, logical_axes: Sequence[Optional[str]]) -> jax.Array:
        if self.mesh is None:
            return x
        spec = logical_to_spec(logical_axes, x.shape, self.mesh, self.rules)
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec))


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm in fp32, cast back.  ``scale`` is the learned gain."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(dt)


def activation(x: jax.Array, kind: str) -> jax.Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(kind)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    """Gemma-2 tanh soft-capping; identity when cap == 0."""
    if not cap:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float, yarn=None) -> jax.Array:
    """(head_dim//2,) inverse frequencies, fp32.  ``yarn`` is
    ``(factor, original_max_pos, beta_fast, beta_slow)``: YaRN blends each
    frequency with its ``factor``-interpolated value along the linear ramp
    between the correction dims of ``beta_fast`` and ``beta_slow``
    rotations over the original context (DeepSeek-V2's rope_scaling)."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    freqs = 1.0 / (theta ** exponent)
    if yarn is None:
        return freqs
    factor, orig, fast, slow = yarn
    low, high = yarn_correction_range(head_dim, theta, orig, fast, slow)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def yarn_correction_range(dim: int, theta: float, orig: int, fast: float,
                          slow: float) -> tuple:
    """(low, high) rotary dims between which YaRN ramps from the original
    frequencies to the interpolated ones."""
    def dim_of(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))
    return (max(math.floor(dim_of(fast)), 0),
            min(math.ceil(dim_of(slow)), dim - 1))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term, 0.1·m·ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn=None) -> jax.Array:
    """Rotate ``x (..., seq, heads, head_dim)`` at absolute ``positions (seq,)``
    (or broadcastable ``(..., seq)``); ``yarn`` as in :func:`rope_freqs`."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta, yarn)                 # (hd/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs   # (..., seq, hd/2)
    ang = ang[..., None, :]                             # broadcast over heads
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def dense_ffn(p, x: jax.Array, act: str, ctx: Ctx) -> jax.Array:
    """SwiGLU MLP: wd( act(x wg) * (x wu) )."""
    h = activation(x @ p["wg"], act) * (x @ p["wu"])
    h = ctx.constrain(h, ("batch", "seq", "ffn"))
    return h @ p["wd"]
