"""Public jit'd entry points for the kernel layer.

The kernels target the TPU. Off the TPU they run under the Pallas
interpreter against the ``ref.py`` oracles: ``interpret_mode()`` is true
exactly when JAX's default backend is the CPU. It is resolved at each
call, never at import, so importing this package initialises no backend.
An explicit ``interpret=`` argument at a call site still wins.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .fft_r2 import fft_r2
from .flash_attention import flash_attention
from .mgs_qrd import mgs_qrd
from .simt_alu import simt_alu
from .wavefront_dot import wavefront_dot


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted: on the CPU backend only."""
    return jax.default_backend() == "cpu"


def alu(op, typ, a, b, mask, old, **kw):
    kw.setdefault("interpret", interpret_mode())
    return simt_alu(jnp.asarray(op), jnp.asarray(typ), a, b, mask, old, **kw)


def dot(a, b, mask=None, mode=0, **kw):
    kw.setdefault("interpret", interpret_mode())
    if mask is None:
        mask = jnp.ones(a.shape, jnp.float32)
    return wavefront_dot(a, b, mask, jnp.asarray(mode), **kw)


def qrd(a, **kw):
    kw.setdefault("interpret", interpret_mode())
    return mgs_qrd(a, **kw)


def fft(re, im, **kw):
    kw.setdefault("interpret", interpret_mode())
    return fft_r2(re, im, **kw)


def flash(q, k, v, **kw):
    kw.setdefault("interpret", interpret_mode())
    return flash_attention(q, k, v, **kw)


__all__ = ["alu", "dot", "qrd", "fft", "flash", "ref", "interpret_mode"]
