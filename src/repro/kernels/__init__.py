"""Pallas TPU kernels for the perf-critical compute layers.

* ``dse_eval``        — MOSAIC's own hot loop: per-(config x op) roofline
                        pre-filter for the stratified sweep (the paper's
                        2.94 M-sample stage), BlockSpec-tiled over config
                        and op blocks.
* ``flash_attention`` — blocked online-softmax attention (32k prefill).
* ``ssm_scan``        — Mamba2 SSD chunked scan (mamba2/jamba mixers).
* ``horner``          — Horner-rule polynomial evaluation (the paper's
                        polynomial SFU, §3.3.1).

Each kernel ships with a pure-jnp oracle in ``ref.py`` and a jit'd
dispatch wrapper in ``ops.py``; tests sweep shapes/dtypes calling the
``*_pallas`` kernels with ``interpret=True`` (they run on the CPU; TPU is
the target).
"""
from .ops import dse_eval, flash_attention, ssm_scan, horner

__all__ = ["dse_eval", "flash_attention", "ssm_scan", "horner"]
