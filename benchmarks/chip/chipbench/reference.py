"""Plain references, the comparisons that decide ``correct``, and the control.

The references are numpy in float64 and import nothing of the system under
test: ``np.fft.fft`` for an FFT; for a QR decomposition ``Q @ R`` against
the input matrix, ``Q^T Q`` against the identity and R's lower triangle
against zero; and the float64 sum for a reduction. Each comparison
returns one number per answer, a relative error, and the harness takes the
largest over every answer the window produced.

The control is the same work computed one precision step below what the
configuration states. The eGPU computes IEEE float32, which on a TPU is a
float32 matrix product at ``highest``; the step below is ``high``: three
bfloat16 passes, ``a_hi*b_hi + a_hi*b_lo + a_lo*b_hi`` with ``a_hi`` the
bfloat16 rounding of ``a`` and ``a_lo`` that of the rest. Every product of
the control is formed that way, explicitly, so it reads the same on the
TPU and on the CPU. Additions stay float32.
"""
from __future__ import annotations

import functools

import numpy as np


# ---------------------------------------------------------------------------
# comparisons (float64 numpy)
# ---------------------------------------------------------------------------

def fft_rel_err(xs: np.ndarray, X: np.ndarray) -> np.ndarray:
    """Per signal: max |X - fft(x)| over max |fft(x)|."""
    xs = np.asarray(xs, np.complex128).reshape(-1, np.shape(xs)[-1])
    X = np.asarray(X, np.complex128).reshape(xs.shape)
    ref = np.fft.fft(xs, axis=-1)
    return np.abs(X - ref).max(axis=-1) / np.abs(ref).max(axis=-1)


def qr_rel_err(As: np.ndarray, Q: np.ndarray, R: np.ndarray) -> np.ndarray:
    """Per matrix, the largest of three readings of a QR decomposition:

    - the residual, max |Q R - A| / max |A|;
    - the loss of orthogonality, max |Q^T Q - I| / cond(A): modified
      Gram-Schmidt keeps |Q^T Q - I| under a small multiple of the unit
      roundoff times the 2-norm condition number of A (Bjorck, 1967), so
      the quotient reads alike on well and badly conditioned matrices;
    - R's strict lower triangle, max |tril(R, -1)| / max |A|.

    Q and R that multiply back to A but skip the orthogonalization (Q the
    columns of A normalized, R diagonal) read near the cosine of the angle
    between two columns over cond(A), far above rounding."""
    As = np.asarray(As, np.float64).reshape(-1, 16, 16)
    Q = np.asarray(Q, np.float64).reshape(As.shape)
    R = np.asarray(R, np.float64).reshape(As.shape)
    scale = np.abs(As).max(axis=(1, 2))
    resid = np.abs(Q @ R - As).max(axis=(1, 2)) / scale
    gram = np.swapaxes(Q, 1, 2) @ Q - np.eye(16)
    orth = np.abs(gram).max(axis=(1, 2)) / np.linalg.cond(As)
    lower = np.abs(np.tril(R, -1)).max(axis=(1, 2)) / scale
    return np.maximum(resid, np.maximum(orth, lower))


def sum_rel_err(xs: np.ndarray, totals: np.ndarray) -> np.ndarray:
    """Per vector: |total - sum(x)| over sum |x|, the sum in float64."""
    xs = np.asarray(xs, np.float64).reshape(len(totals), -1)
    totals = np.asarray(totals, np.float64)
    return np.abs(totals - xs.sum(axis=1)) / np.abs(xs).sum(axis=1)


# ---------------------------------------------------------------------------
# the control: the references at three-pass bfloat16 ("high")
# ---------------------------------------------------------------------------

def _bf16(v):
    """Round float32 to the nearest bfloat16 (ties to even), kept in
    float32. Done on the bits, so that no compiler can drop the rounding
    as it may drop a float32 -> bfloat16 -> float32 round trip."""
    import jax
    import jax.numpy as jnp

    u = jax.lax.bitcast_convert_type(v, jnp.uint32)
    u = (u + jnp.uint32(0x7FFF) + ((u >> 16) & 1)) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _mul3(a, b):
    """float32 product formed as a TPU ``high`` matmul forms it."""
    def split(v):
        hi = _bf16(v)
        return hi, _bf16(v - hi)

    a_hi, a_lo = split(a)
    b_hi, b_lo = split(b)
    return a_hi * b_hi + (a_hi * b_lo + a_lo * b_hi)


def _bitrev(n: int) -> np.ndarray:
    bits = n.bit_length() - 1
    idx = np.arange(n)
    return np.array([int(format(i, f"0{bits}b")[::-1], 2) for i in idx])


@functools.lru_cache(maxsize=None)
def _fft_control_fn(n: int):
    import jax
    import jax.numpy as jnp

    perm = _bitrev(n)

    @jax.jit
    def run(re, im):
        b = re.shape[0]
        re, im = re[:, perm], im[:, perm]
        h = 1
        while h < n:                        # radix-2 DIT butterflies
            k = np.arange(h)
            w = np.exp(-2j * np.pi * k / (2 * h))
            w_re = jnp.asarray(w.real, jnp.float32)
            w_im = jnp.asarray(w.imag, jnp.float32)
            re4 = re.reshape(b, n // (2 * h), 2, h)
            im4 = im.reshape(b, n // (2 * h), 2, h)
            a_re, b_re = re4[:, :, 0], re4[:, :, 1]
            a_im, b_im = im4[:, :, 0], im4[:, :, 1]
            t_re = _mul3(b_re, w_re) - _mul3(b_im, w_im)
            t_im = _mul3(b_re, w_im) + _mul3(b_im, w_re)
            re = jnp.stack([a_re + t_re, a_re - t_re], axis=2).reshape(b, n)
            im = jnp.stack([a_im + t_im, a_im - t_im], axis=2).reshape(b, n)
            h *= 2
        return re, im

    return run


def fft_control(xs: np.ndarray) -> np.ndarray:
    xs = np.asarray(xs, np.complex64)
    re, im = _fft_control_fn(int(xs.shape[-1]))(
        np.ascontiguousarray(xs.real), np.ascontiguousarray(xs.imag))
    return np.asarray(re) + 1j * np.asarray(im)


@functools.lru_cache(maxsize=None)
def _qr_control_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(A):                             # modified Gram-Schmidt
        V = A
        Q = jnp.zeros_like(A)
        R = jnp.zeros_like(A)
        col = jnp.arange(16)
        for j in range(16):
            v = V[:, :, j]
            nrm = jnp.sqrt(jnp.sum(_mul3(v, v), axis=1))
            q = _mul3(v, (1.0 / nrm)[:, None])
            Q = Q.at[:, :, j].set(q)
            r = jnp.sum(_mul3(q[:, :, None], V), axis=1)     # (b, 16)
            r = jnp.where(col > j, r, 0.0).at[:, j].set(nrm)
            R = R.at[:, j, :].set(r)
            upd = _mul3(q[:, :, None], r[:, None, :])
            V = jnp.where(col[None, None, :] > j, V - upd, 0.0)
        return Q, R

    return run


def qr_control(As: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    Q, R = _qr_control_fn()(np.asarray(As, np.float32))
    return np.asarray(Q), np.asarray(R)


@functools.lru_cache(maxsize=None)
def _sum_control_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(x):                             # x @ ones at three passes
        return jnp.sum(_mul3(x, jnp.ones_like(x)), axis=-1)

    return run


def sum_control(xs: np.ndarray) -> np.ndarray:
    return np.asarray(_sum_control_fn()(np.asarray(xs, np.float32)))
