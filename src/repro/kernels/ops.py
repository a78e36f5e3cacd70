"""Jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile natively; on CPU (this container, CI) they run
under ``interpret=True`` which executes the kernel body in Python — the
correctness path used by the test suite's allclose sweeps against ref.py.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import (decode_attention as _da, flash_attention as _fa,
                           gbm_predict as _gp, mamba_scan as _ms, wkv6 as _wk)


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "q_block", "kv_block"))
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    q_block=512, kv_block=512):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, q_block=q_block,
                               kv_block=kv_block, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window", "softcap", "block"))
def decode_attention(q, k_cache, v_cache, pos, *, window=0, softcap=0.0,
                     block=1024):
    return _da.decode_attention(q, k_cache, v_cache, pos, window=window,
                                softcap=softcap, block=block,
                                interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6(r, k, v, w, u, s0=None, *, chunk=16):
    return _wk.wkv6(r, k, v, w, u, s0, chunk=chunk, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk", "d_block"))
def mamba_scan(u, dt, A, B_in, C_in, h0=None, *, chunk=64, d_block=512):
    return _ms.mamba_scan(u, dt, A, B_in, C_in, h0, chunk=chunk,
                          d_block=d_block, interpret=_interpret())


@jax.jit
def gbm_predict(X, feat, thr, leaf, f0, y_scale=1.0):
    return _gp.gbm_predict(X, feat, thr, leaf, f0, y_scale,
                           interpret=_interpret())
