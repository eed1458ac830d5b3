"""MMSE-optimal quantization ranges (paper Eq. 5, Appendix C).

- PPQ (Progressive Projection Quantization, Algorithm 1, adopted from [14]):
  iterative linear-projection solution of ``min_s ||W - s*clip(round(W/s))||``.
  At convergence the error is orthogonal to the quantized tensor (Eq. 14).
- APQ (Alternating Projection Quantization, Algorithm 2, *novel in the paper*):
  the inseparable doubly-channelwise problem ``min_{S,T} ||X - S_i T_j q_ij||``
  solved by alternating row/column projections.

All routines are pure jnp + lax.fori_loop → jit/vmap-able, used both at
initialization time and by the paper reproduction (paper/).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .fakequant import expand_group_scale, qrange

_EPS = 1e-12


def _proj_scale(w: jax.Array, q: jax.Array, axes, keepdims=True) -> jax.Array:
    """Optimal linear-projection scale  s = <q, w> / <q, q>  (Eq. 14)."""
    num = jnp.sum(q * w, axis=axes, keepdims=keepdims)
    den = jnp.sum(q * q, axis=axes, keepdims=keepdims)
    return num / jnp.maximum(den, _EPS)


def ppq_scale(w: jax.Array, bits: int, axes=None, iters: int = 10) -> jax.Array:
    """Algorithm 1.  ``axes``: reduction axes treated as one slice.

    axes=None   -> scalar (per-tensor / layerwise) scale, shape () broadcastable
    axes=(0,)   -> per-column (per-out-channel) scales for W[in, out]
    axes=(1,)   -> per-row (per-in-channel) scales
    Returns a scale with ``keepdims=True`` shape for direct broadcasting.
    """
    if axes is None:
        axes = tuple(range(w.ndim))
    lo, hi = qrange(bits, signed=True)
    s0 = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / hi
    s0 = jnp.maximum(s0, _EPS)

    def body(_, s):
        q = jnp.clip(jnp.round(w / s), lo, hi)
        s_new = _proj_scale(w, q, axes)
        # guard collapsed slices (all-zero q)
        return jnp.where(s_new > _EPS, s_new, s)

    return jax.lax.fori_loop(0, iters, body, s0)


def ppq_scale_grouped(w: jax.Array, bits: int, n_groups: int,
                      iters: int = 10) -> jax.Array:
    """Group-wise PPQ along the in-dim of ``W[in, out]`` → ``[n_groups, out]``.

    Each (in-group, out-channel) block of ``in/n_groups`` weights is one MMSE
    slice — the group-layout analogue of Eq. 5b, reducing over the block axis
    only.  Used to fit ``log_swr`` for QLayout('group', g) linears.
    """
    K, N = w.shape
    assert K % n_groups == 0, (K, n_groups)
    wg = w.reshape(n_groups, K // n_groups, N)
    return ppq_scale(wg, bits, axes=(1,), iters=iters)[:, 0, :]


def mmse_error(w: jax.Array, scale: jax.Array, bits: int) -> jax.Array:
    """||W - s*clip(round(W/s))||_2  for a given (broadcastable) scale."""
    lo, hi = qrange(bits, signed=True)
    deq = scale * jnp.clip(jnp.round(w / scale), lo, hi)
    return jnp.linalg.norm((w - deq).reshape(-1))


def apq_scales(w: jax.Array, bits: int, iters: int = 10) -> tuple[jax.Array, jax.Array]:
    """Algorithm 2 (APQ) for W[in(m), out(n)] → (S_wL[m,1], S_wR[1,n]).

    Init per the paper:  T_j ← max_i|X_ij|/qmax;  S_i ← max_j|X_ij/T_j|/qmax,
    then alternate single projection iterations over columns / rows.
    The solution is unique only up to a scalar shuttled between S and T.
    """
    lo, hi = qrange(bits, signed=True)
    t = jnp.max(jnp.abs(w), axis=0, keepdims=True) / hi          # [1, n]
    t = jnp.maximum(t, _EPS)
    s = jnp.max(jnp.abs(w / t), axis=1, keepdims=True) / hi      # [m, 1]
    s = jnp.maximum(s, _EPS)

    def body(_, st):
        s, t = st
        q = jnp.clip(jnp.round(w / (s * t)), lo, hi)
        # column update: effective target is X/S with per-element q
        t_new = _proj_scale(w / s, q, axes=(0,))                 # [1, n]
        t = jnp.where(t_new > _EPS, t_new, t)
        q = jnp.clip(jnp.round(w / (s * t)), lo, hi)
        s_new = _proj_scale(w / t, q, axes=(1,))                 # [m, 1]
        s = jnp.where(s_new > _EPS, s_new, s)
        return s, t

    s, t = jax.lax.fori_loop(0, iters, body, (s, t))
    return s, t


def mmse_lw(w: jax.Array, bits: int, iters: int = 10) -> jax.Array:
    """Layerwise (scalar) MMSE error — Eq. 5a."""
    return mmse_error(w, ppq_scale(w, bits, axes=None, iters=iters), bits)


def mmse_ch(w: jax.Array, bits: int, iters: int = 10) -> jax.Array:
    """Channelwise (per-out-channel) MMSE error — Eq. 5b (W as [in, out])."""
    return mmse_error(w, ppq_scale(w, bits, axes=(0,), iters=iters), bits)


def mmse_dch(w: jax.Array, bits: int, iters: int = 10) -> jax.Array:
    """Doubly-channelwise MMSE error — Eq. 5c via APQ."""
    s, t = apq_scales(w, bits, iters=iters)
    return mmse_error(w, s * t, bits)


def mmse_grp(w: jax.Array, bits: int, group: int, iters: int = 10) -> jax.Array:
    """Group-wise MMSE error (between Eq. 5a and 5b on the granularity ladder)."""
    K = w.shape[0]
    n_g = K // group if K % group == 0 else 1
    s = ppq_scale_grouped(w, bits, n_g, iters=iters)        # [n_g, out]
    return mmse_error(w, expand_group_scale(s, K, axis=0), bits)
