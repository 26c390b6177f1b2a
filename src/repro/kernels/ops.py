"""Public wrappers for the Pallas kernels.

Handle batch-dim flattening, tile padding, scale defaulting, and backend
dispatch: on CPU (this container) kernels run in interpret mode — the
kernel *body* executes in Python for correctness validation; on TPU the
same code lowers to Mosaic. `interpret=None` means auto.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import bitserial as _bitserial
from repro.kernels import nm_spmm as _nm_spmm
from repro.kernels import quant_matmul as _quant_matmul
from repro.kernels import sparse_conv1d as _sparse_conv1d
from repro.kernels._common import flatten_batch, pad_to


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def _ones_scale(n: int) -> jax.Array:
    return jnp.ones((1, n), jnp.float32)


def nm_spmm(
    x: jax.Array,
    values: jax.Array,
    select: jax.Array,
    scale: Optional[jax.Array] = None,
    *,
    group_size: int,
    keep: int,
    block_m: int = 128,
    block_n: int = 128,
    block_groups: int = 16,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Balanced select-index sparse matmul (..., K) x (Kk, N) -> (..., N).

    K (dense contraction of `x`) must equal (Kk // keep) * group_size —
    i.e. `x` is already group-padded, as `core.compiler` guarantees.
    """
    kk, n = values.shape
    x2, lead = flatten_batch(x)
    m, k = x2.shape
    assert kk % keep == 0 and k == (kk // keep) * group_size, (k, kk)
    sc = scale if scale is not None else _ones_scale(n)
    sc = sc.reshape(1, n).astype(jnp.float32)
    # pad M and N to tile multiples; K is tiled in whole groups already.
    bm = min(block_m, max(8, m))
    bn = min(block_n, n)
    xp = pad_to(x2, 0, bm)
    vp = pad_to(values, 1, bn)
    sp = pad_to(select, 1, bn)
    scp = pad_to(sc, 1, bn)
    groups = k // group_size
    gpb = block_groups
    while groups % gpb:
        gpb //= 2
    if (gpb * group_size) % 128:
        # Mosaic needs the x block's K to be a multiple of 128 lanes or
        # the whole K; the small VA layers (K <= 192) take the whole K
        gpb = groups
    y = _nm_spmm.nm_spmm_2d(
        xp, vp, sp, scp,
        group_size=group_size, keep=keep,
        block_m=bm, block_n=bn, block_groups=gpb,
        interpret=_auto_interpret(interpret),
    )[:m, :n]
    return y.reshape(*lead, n)


def bitserial_matmul(
    x: jax.Array,
    packed: jax.Array,
    scale: Optional[jax.Array] = None,
    *,
    bits: int,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """CMUL bit-plane matmul (..., K) x packed(K*bits/8, N) -> (..., N)."""
    vpb = 8 // bits
    kp, n = packed.shape
    k = kp * vpb
    x2, lead = flatten_batch(x)
    m, kx = x2.shape
    assert kx == k, (kx, k)
    sc = (scale if scale is not None else _ones_scale(n)).reshape(1, n)
    bm = min(block_m, max(8, m))
    bn = min(block_n, n)
    bk = min(block_k, k)
    while k % bk or bk % vpb:
        bk //= 2
    xp = pad_to(x2, 0, bm)
    pp = pad_to(packed, 1, bn)
    scp = pad_to(sc.astype(jnp.float32), 1, bn)
    y = _bitserial.bitserial_matmul_2d(
        xp, pp, scp, bits=bits,
        block_m=bm, block_n=bn, block_k=bk,
        interpret=_auto_interpret(interpret),
    )[:m, :n]
    return y.reshape(*lead, n)


def quant_matmul(
    x: jax.Array,
    packed: jax.Array,
    scale: Optional[jax.Array] = None,
    *,
    bits: int,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Packed dequant matmul (single MXU pass) — serving path."""
    vpb = 8 // bits
    kp, n = packed.shape
    k = kp * vpb
    x2, lead = flatten_batch(x)
    m, kx = x2.shape
    assert kx == k, (kx, k)
    sc = (scale if scale is not None else _ones_scale(n)).reshape(1, n)
    bm = min(block_m, max(8, m))
    bn = min(block_n, n)
    bk = min(block_k, k)
    while k % bk or bk % vpb:
        bk //= 2
    xp = pad_to(x2, 0, bm)
    pp = pad_to(packed, 1, bn)
    scp = pad_to(sc.astype(jnp.float32), 1, bn)
    y = _quant_matmul.quant_matmul_2d(
        xp, pp, scp, bits=bits,
        block_m=bm, block_n=bn, block_k=bk,
        interpret=_auto_interpret(interpret),
    )[:m, :n]
    return y.reshape(*lead, n)


def sparse_conv1d(
    x: jax.Array,
    values: jax.Array,
    select: jax.Array,
    scale: Optional[jax.Array] = None,
    *,
    ksize: int,
    stride: int = 1,
    group_size: int,
    keep: int,
    block_t: int = 64,
    block_n: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused sparse-quantized 1-D conv (B, T, C) -> (B, T_out, N)."""
    kk, n = values.shape
    sc = (scale if scale is not None else _ones_scale(n)).reshape(1, n)
    bn = min(block_n, n)
    vp = pad_to(values, 1, bn)
    sp = pad_to(select, 1, bn)
    scp = pad_to(sc.astype(jnp.float32), 1, bn)
    y = _sparse_conv1d.sparse_conv1d_call(
        x, vp, sp, scp,
        ksize=ksize, stride=stride, group_size=group_size, keep=keep,
        block_t=block_t, block_n=bn,
        interpret=_auto_interpret(interpret),
    )
    return y[..., :n]
