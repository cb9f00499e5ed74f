"""Pure-jnp oracles for every Pallas kernel (the ``assert_allclose`` truth).

These are deliberately straightforward implementations — no tiling, no
memory-space reasoning — used by tests and as CPU fallbacks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# opcode numbering shared with the kernels (subset of core.isa.Op that the
# SIMT ALU executes)
ALU_ADD, ALU_SUB, ALU_MUL = 1, 2, 3
ALU_AND, ALU_OR, ALU_XOR, ALU_NOT = 4, 5, 6, 7
ALU_LSL, ALU_LSR = 8, 9
TYP_INT32, TYP_UINT32, TYP_FP32 = 0, 1, 2


def _sext16(x):
    low = x & 0xFFFF
    return low | (((low >> 15) & 1) * jnp.uint32(0xFFFF0000))


def _static(v):
    """``v`` as a Python int when it is a host constant, else None."""
    if isinstance(v, (int, np.integer)):
        return int(v)
    if isinstance(v, np.ndarray) and v.shape == ():
        return int(v)
    return None


def _mul16(typ, a, b):
    """16x16 -> 32-bit multiply, signed unless ``typ`` is UINT32."""
    def uint():
        return (a & 0xFFFF) * (b & 0xFFFF)

    def sint():
        return _sext16(a) * _sext16(b)

    s_typ = _static(typ)
    if s_typ is not None:
        return uint() if s_typ == TYP_UINT32 else sint()
    return jnp.where(typ == TYP_UINT32, uint(), sint())


# integer-path result of each opcode; any other opcode is LSR
_INT_OPS = {
    ALU_ADD: lambda typ, a, b: a + b,
    ALU_SUB: lambda typ, a, b: a - b,
    ALU_MUL: _mul16,
    ALU_AND: lambda typ, a, b: a & b,
    ALU_OR: lambda typ, a, b: a | b,
    ALU_XOR: lambda typ, a, b: a ^ b,
    ALU_NOT: lambda typ, a, b: ~a,
    ALU_LSL: lambda typ, a, b: a << (b & 31),
}
_FP_OPS = {ALU_ADD: lambda a, b: a + b, ALU_SUB: lambda a, b: a - b,
           ALU_MUL: lambda a, b: a * b}


def _lsr(typ, a, b):
    return a >> (b & 31)


def alu_ref(op: jax.Array, typ: jax.Array, a_u32: jax.Array,
            b_u32: jax.Array) -> jax.Array:
    """eGPU SIMT ALU semantics on uint32 lanes (any shape).

    ``op``/``typ`` may be traced scalars or host constants; constants
    compute only the taken branch. The select chain is nested
    ``where``s (``jnp.select`` lowers through an argmax Mosaic lacks),
    so the same function runs inside Pallas TPU kernels."""
    a_f = jax.lax.bitcast_convert_type(a_u32, jnp.float32)
    b_f = jax.lax.bitcast_convert_type(b_u32, jnp.float32)
    s_op, s_typ = _static(op), _static(typ)
    if s_op is not None and s_typ is not None:
        if s_typ == TYP_FP32 and s_op in _FP_OPS:
            return jax.lax.bitcast_convert_type(_FP_OPS[s_op](a_f, b_f),
                                                jnp.uint32)
        return _INT_OPS.get(s_op, _lsr)(s_typ, a_u32, b_u32)
    res_int = _lsr(typ, a_u32, b_u32)
    for code, fn in _INT_OPS.items():
        res_int = jnp.where(op == code, fn(typ, a_u32, b_u32), res_int)
    res_fp = _FP_OPS[ALU_MUL](a_f, b_f)
    for code in (ALU_SUB, ALU_ADD):
        res_fp = jnp.where(op == code, _FP_OPS[code](a_f, b_f), res_fp)
    res_fp = jax.lax.bitcast_convert_type(res_fp, jnp.uint32)
    fp_op = (typ == TYP_FP32) & ((op == ALU_ADD) | (op == ALU_SUB)
                                 | (op == ALU_MUL))
    return jnp.where(fp_op, res_fp, res_int)


def wavefront_dot_ref(a: jax.Array, b: jax.Array, active: jax.Array,
                      n_sp: int = 16) -> jax.Array:
    """Per-wavefront dot product: (..., n_threads) f32 -> (..., n_waves).

    The eGPU dot unit multiplies a wavefront's a*b lanewise and reduces;
    inactive lanes contribute zero (flexible-ISA masking).
    """
    *lead, n = a.shape
    waves = n // n_sp
    a2 = a.reshape(*lead, waves, n_sp)
    b2 = b.reshape(*lead, waves, n_sp)
    m2 = active.reshape(*lead, waves, n_sp)
    return jnp.sum(jnp.where(m2, a2 * b2, 0.0), axis=-1)


def mgs_qrd_ref(a: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Batched Modified Gram-Schmidt QRD: (B, n, n) -> (Q, R).

    Column version, exactly the eGPU benchmark's math: q_j = a_j/||a_j||
    (via rsqrt, the SFU), r_jk = <q_j, a_k>, a_k -= r_jk q_j. Branch-free:
    already-finished columns have zero residuals.

    The projections contract with an explicit lanewise multiply-then-sum
    (NOT ``einsum``/``dot_general``): that is what the eGPU dot-product
    unit does, and it keeps the oracle's f32 accumulation order identical
    to the ``mgs_qrd`` Pallas kernel's — in interpret mode the two are
    bitwise equal, so kernel-vs-ref sweeps can assert tight tolerances
    on any input (a dot_general here drifted up to ~1e-3 on
    ill-conditioned draws purely from summation order).
    """
    B, n, _ = a.shape
    q = jnp.zeros_like(a)
    r = jnp.zeros_like(a)
    eye = jnp.eye(n, dtype=a.dtype)

    def body(j, carry):
        res, q, r = carry
        onehot = eye[j]                                     # (n,)
        aj = jnp.sum(res * onehot[None, None, :], axis=2)   # (B, n)
        # "twice is enough" re-orthogonalization, mirrored in the kernel:
        # project the residual once more against the computed Q columns
        # and fold the coefficients into R column j
        coeff = jnp.sum(q * aj[:, :, None], axis=1)
        corr = jnp.sum(q * coeff[:, None, :], axis=2)
        aj = aj - corr
        res = res - corr[:, :, None] * onehot[None, None, :]
        r = r + coeff[:, :, None] * onehot[None, None, :]
        recip = jax.lax.rsqrt(jnp.sum(aj * aj, axis=1, keepdims=True))
        qj = aj * recip                                     # (B, n)
        rrow = jnp.sum(qj[:, :, None] * res, axis=1)        # (B, n)
        res = res - qj[:, :, None] * rrow[:, None, :]
        q = q + qj[:, :, None] * onehot[None, None, :]
        r = r + rrow[:, None, :] * onehot[None, :, None]
        return res, q, r

    _, q, r = jax.lax.fori_loop(0, n, body, (a, q, r))
    return q, r


def fft_r2_ref(re: jax.Array, im: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Batched radix-2 DIF FFT, natural-order output: (B, N) f32 planes."""
    x = (re + 1j * im).astype(jnp.complex64)
    y = jnp.fft.fft(x, axis=-1)
    return jnp.real(y).astype(jnp.float32), jnp.imag(y).astype(jnp.float32)


def fft_r2_ref_br(re: jax.Array, im: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Same, but in the kernel's bit-reversed output order."""
    n = re.shape[-1]
    rr, ri = fft_r2_ref(re, im)
    idx = bitrev(n)
    return rr[..., idx], ri[..., idx]


def bitrev(n: int) -> np.ndarray:
    bits = n.bit_length() - 1
    idx = np.arange(n)
    out = np.zeros(n, dtype=np.int64)
    for b in range(bits):
        out |= ((idx >> b) & 1) << (bits - 1 - b)
    return out
