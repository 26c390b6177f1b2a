"""sparse_conv1d — one fused VA-net layer (im2col + SPE matmul) in Pallas.

The chip streams the ifmap through the shared SPad and never materializes
im2col patches in memory; this kernel does the same on TPU: the input tile
lives once in VMEM, windows are cut *inside* the kernel (static
unit-stride slices), and the compressed weights are decompressed in VMEM
and fed to the MXU. HBM traffic: the raw signal + the compressed weight
stream only.

A stride s > 1 is taken out of the kernel: the host splits the padded row
into its s phases (``x[p::s]``), and tap i of output t reads phase
``i % s`` at ``t + i // s``. Every in-VMEM slice then has unit stride,
which Mosaic lowers (a strided sublane slice it refuses).

Shapes are the VA detector's (T<=512, C<=96, N<=96), so a whole (1, T, C)
row plus all weights fit in VMEM trivially; the grid walks
(batch, T_out tiles, N tiles).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._common import decompress_tile


def _kernel(
    x_ref,  # (1, stride, T_ph, C) float — the row's stride phases in VMEM
    v_ref,  # (Kk, bn)
    s_ref,  # (Kk, bn)
    scale_ref,  # (1, bn)
    o_ref,  # (1, bt, bn) f32
    *,
    ksize: int,
    stride: int,
    group_size: int,
    keep: int,
    block_t: int,
    k_dense: int,
):
    bt = block_t
    t0 = pl.program_id(1) * bt  # phase-row start of this output tile
    span = bt + (ksize - 1) // stride
    wins = [
        x_ref[0, p, pl.ds(t0, span), :].astype(jnp.float32)  # (span, C)
        for p in range(min(stride, ksize))
    ]
    # im2col inside VMEM: row-order (tap, channel) == compiler's flatten.
    cols = [
        wins[i % stride][i // stride : i // stride + bt, :]
        for i in range(ksize)
    ]
    patches = jnp.concatenate(cols, axis=-1)  # (bt, ksize*C)
    if patches.shape[-1] < k_dense:  # group padding (zeros, like the chip)
        patches = jnp.pad(
            patches, ((0, 0), (0, k_dense - patches.shape[-1]))
        )
    w = decompress_tile(v_ref[...], s_ref[...], group_size, keep)
    y = jnp.dot(patches, w, preferred_element_type=jnp.float32)
    o_ref[0, :, :] = y * scale_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=(
        "ksize", "stride", "group_size", "keep", "block_t", "block_n",
        "interpret",
    ),
)
def sparse_conv1d_call(
    x: jax.Array,  # (B, T, C) — unpadded signal
    values: jax.Array,  # (Kk, N)
    select: jax.Array,  # (Kk, N) uint8
    scale: jax.Array,  # (1, N)
    *,
    ksize: int,
    stride: int,
    group_size: int,
    keep: int,
    block_t: int = 64,
    block_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, t, c = x.shape
    kk, n = values.shape
    k_dense = (kk // keep) * group_size
    assert k_dense >= ksize * c, (k_dense, ksize, c)
    t_out = (t - 1) // stride + 1
    # SAME padding (XLA convention), applied host-side once.
    pad_total = max((t_out - 1) * stride + ksize - t, 0)
    pad_l = pad_total // 2
    bt = min(block_t, t_out)
    nt = pl.cdiv(t_out, bt)
    # pad T so every tile's input span is in-bounds, to whole phases
    t_ph = nt * bt + (ksize - 1) // stride
    xp = jnp.pad(
        x, ((0, 0), (pad_l, max(t_ph * stride - t - pad_l, 0)), (0, 0))
    )[:, : t_ph * stride]
    # (B, T_ph*stride, C) -> (B, stride, T_ph, C): phase p is xp[p::stride]
    xp = xp.reshape(b, t_ph, stride, c).transpose(0, 2, 1, 3)
    bn = min(block_n, n)
    grid = (b, nt, pl.cdiv(n, bn))
    out = pl.pallas_call(
        functools.partial(
            _kernel,
            ksize=ksize,
            stride=stride,
            group_size=group_size,
            keep=keep,
            block_t=bt,
            k_dense=k_dense,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, stride, t_ph, c), lambda bi, ti, ni: (bi, 0, 0, 0)
            ),
            pl.BlockSpec((kk, bn), lambda bi, ti, ni: (0, ni)),
            pl.BlockSpec((kk, bn), lambda bi, ti, ni: (0, ni)),
            pl.BlockSpec((1, bn), lambda bi, ti, ni: (0, ni)),
        ],
        out_specs=pl.BlockSpec((1, bt, bn), lambda bi, ti, ni: (bi, ti, ni)),
        out_shape=jax.ShapeDtypeStruct((b, nt * bt, n), jnp.float32),
        interpret=interpret,
    )(xp, values, select, scale)
    return out[:, :t_out, :]
