"""Pure-jnp oracles (the correctness ground truth) for the count-sketch
kernel and the sketch families' Grams; ``fwht`` is also the Hadamard mix
the SRHT family runs."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def count_sketch_apply(h: jax.Array, sigma: jax.Array, a: jax.Array,
                       block_size: int) -> jax.Array:
    """S^T A for all sketch blocks.

    h:     (K, n) int32 bucket indices in [0, block_size)
    sigma: (K, n) Rademacher signs
    a:     (n, d)
    ->     (K, block_size, d)
    """
    def one(hk, sk):
        return jax.ops.segment_sum(a * sk[:, None].astype(a.dtype), hk,
                                   num_segments=block_size)
    return jax.vmap(one)(h, sigma)


def sjlt_apply(h: jax.Array, sigma: jax.Array, a: jax.Array,
               block_size: int) -> jax.Array:
    """SJLT (OSNAP) apply: s signed segment-sum layers per block, / sqrt(s).

    h:     (K, s, n) int32 bucket indices in [0, block_size)
    sigma: (K, s, n) Rademacher signs
    a:     (n, d)
    ->     (K, block_size, d)
    """
    s = h.shape[1]

    def one(hk, sk):
        def slot(ht, st):
            return jax.ops.segment_sum(a * st[:, None].astype(a.dtype), ht,
                                       num_segments=block_size)
        return jax.vmap(slot)(hk, sk).sum(axis=0)

    out = jax.vmap(one)(h, sigma)
    return out / jnp.sqrt(jnp.asarray(float(s), out.dtype))


def oversketch_gram(a_tilde: jax.Array, survivors: jax.Array) -> jax.Array:
    """H_hat = (1/N_avail) sum_k m_k A_tilde_k^T A_tilde_k.

    a_tilde: (K, b, d); survivors: (K,) bool -> (d, d)
    """
    m = survivors.astype(a_tilde.dtype)
    n_avail = jnp.maximum(m.sum(), 1.0)
    return jnp.einsum("k,kbd,kbe->de", m, a_tilde, a_tilde) / n_avail


def fwht(x: jax.Array) -> jax.Array:
    """Orthonormal Walsh-Hadamard transform along axis 1 of (K, n, d).

    Radix-2 butterfly (Sylvester / natural ordering).  n must be a power
    of two.
    """
    k, n, d = x.shape
    if n & (n - 1):
        raise ValueError(f"fwht length {n} must be a power of two")

    def one(xb):
        y, h = xb, 1
        while h < n:
            y = y.reshape(n // (2 * h), 2, h, d)
            y = jnp.stack([y[:, 0] + y[:, 1], y[:, 0] - y[:, 1]], axis=1)
            y = y.reshape(n, d)
            h *= 2
        return y / jnp.sqrt(jnp.asarray(n, y.dtype))

    return jax.vmap(one)(x)


def srht_apply(rows: jax.Array, sigma: jax.Array, a: jax.Array) -> jax.Array:
    """Blocked SRHT apply, the oracle: sign, zero-pad to n_pad = next
    power of two, orthonormal FWHT, gather the b sampled rows, scale by
    sqrt(n_pad/b).

    rows: (K, b) int32 sampled Hadamard-row indices in [0, n_pad)
    sigma: (K, n) Rademacher signs
    a:    (n, d)
    ->    (K, b, d)
    """
    n, d = a.shape
    n_pad = 1 << max(0, (n - 1).bit_length())
    b = rows.shape[1]
    scale = jnp.sqrt(jnp.asarray(n_pad / b, jnp.float32))

    def one(rk, sk):
        x = sk[:, None] * a.astype(jnp.float32)
        if n_pad != n:
            x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
        return fwht(x[None])[0][rk] * scale

    return jax.vmap(one)(rows, sigma)


def sketch_gram_count(h: jax.Array, sigma: jax.Array, a: jax.Array,
                      block_size: int, survivors: jax.Array) -> jax.Array:
    """Apply then masked Gram: the count-sketch family's Gram oracle."""
    return oversketch_gram(count_sketch_apply(h, sigma, a, block_size),
                           survivors)


def sketch_gram_srht(rows: jax.Array, sigma: jax.Array, a: jax.Array,
                     survivors: jax.Array) -> jax.Array:
    """Apply then masked Gram: the SRHT family's Gram oracle."""
    return oversketch_gram(srht_apply(rows, sigma, a), survivors)


def sketch_gram_sjlt(h: jax.Array, sigma: jax.Array, a: jax.Array,
                     block_size: int, survivors: jax.Array) -> jax.Array:
    """Apply then masked Gram: the SJLT family's Gram oracle."""
    return oversketch_gram(sjlt_apply(h, sigma, a, block_size), survivors)

