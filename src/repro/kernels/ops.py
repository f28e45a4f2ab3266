"""Jit'd dispatch wrappers for the Pallas kernels.

The kernels compile for the TPU; nothing picks interpret mode from the
backend (tests call the ``*_pallas`` functions with ``interpret=True``).
``use_pallas=False`` falls back to the ref oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .dse_eval import dse_eval_pallas
from .flash_attention import flash_attention_pallas
from .horner import horner_pallas
from .ssm_scan import ssm_scan_pallas

__all__ = ["dse_eval", "flash_attention", "ssm_scan", "horner"]


@functools.partial(jax.jit, static_argnames=("causal", "use_pallas"))
def flash_attention(q, k, v, causal: bool = True, use_pallas: bool = True):
    if not use_pallas:
        return ref.flash_attention_ref(q, k, v, causal)
    return flash_attention_pallas(q, k, v, causal)


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas"))
def ssm_scan(x, dt, a_log, b, c, chunk: int = 128, use_pallas: bool = True):
    if not use_pallas:
        return ref.ssm_scan_ref(x, dt, a_log, b, c, chunk)
    return ssm_scan_pallas(x, dt, a_log, b, c, chunk)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def horner(x, coeffs, use_pallas: bool = True):
    if not use_pallas:
        return ref.horner_ref(x, coeffs)
    return horner_pallas(x, coeffs)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def dse_eval(tiles, ops, use_pallas: bool = True):
    if not use_pallas:
        return ref.dse_eval_ref(tiles, ops)
    return dse_eval_pallas(tiles, ops)
