"""bitserial_matmul — the CMUL as a Pallas TPU kernel.

The chip's configurable multiplier splits each B-bit weight into 1-bit
segments, multiplies each against the selected activation, and
shift-accumulates. The TPU-native twin: per VMEM tile, unpack the packed
two's-complement planes and run one MXU matmul per plane:

    y = sum_b s_b 2^b (x @ W_b),   s_b = -1 for the sign plane else +1

Numerically identical to dequant-then-matmul (asserted in tests). HBM
traffic is the packed size (bits/8 bytes per weight) — this is how sub-byte
(4/2/1-bit) layers pay for only what they store, without native int4
dtypes. For 8-bit layers prefer `quant_matmul` (1 MXU pass, same bytes);
the plane loop is the *faithful* CMUL arithmetic and the sub-byte path.

Tiling (defaults): x (128, 256) f32 + packed (256*bits/8, 128) u8 +
out (128, 128) f32 + per-plane {0,1} tile (256, 128) f32 — ≪ VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, p_ref, scale_ref, o_ref, *, bits: int, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    packed = p_ref[...]  # (bk/vpb, bn) uint8
    vpb = 8 // bits
    kp, bn = packed.shape
    mask = (1 << bits) - 1
    # int32, not uint32: Mosaic has no uint32 -> float32 cast, and the
    # masked words are < 2**bits, so the signed view is exact
    shifts = (jnp.arange(vpb, dtype=jnp.int32) * bits).reshape(1, vpb, 1)
    u = (packed.astype(jnp.int32)[:, None, :] >> shifts) & mask
    u = u.reshape(kp * vpb, bn)  # unsigned two's-complement words (bk, bn)

    if bits == 1:
        # plane in {0,1} encodes {-1,+1}: w = 2p - 1
        p = u.astype(jnp.float32)
        acc = 2.0 * jnp.dot(x, p, preferred_element_type=jnp.float32)
        acc -= jnp.sum(x, axis=-1, keepdims=True)
    else:
        acc = jnp.zeros_like(o_ref)
        for b in range(bits):  # static: one MXU pass per plane
            plane = ((u >> b) & 1).astype(jnp.float32)
            coeff = -(2.0 ** (bits - 1)) if b == bits - 1 else 2.0**b
            acc += coeff * jnp.dot(
                x, plane, preferred_element_type=jnp.float32
            )
    o_ref[...] += acc

    @pl.when(pl.program_id(2) == nk - 1)
    def _scale():
        o_ref[...] *= scale_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("bits", "block_m", "block_n", "block_k", "interpret"),
)
def bitserial_matmul_2d(
    x: jax.Array,  # (M, K)
    packed: jax.Array,  # (K * bits / 8, N) uint8 — `quant.pack_planes`
    scale: jax.Array,  # (1, N) f32
    *,
    bits: int,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    kp, n = packed.shape
    vpb = 8 // bits
    assert kp * vpb == k, f"packed rows {kp} x {vpb} != K={k}"
    bm = min(block_m, m)
    bn = min(block_n, n)
    bk = min(block_k, k)
    assert bk % vpb == 0 and k % bk == 0, (bk, vpb, k)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
    return pl.pallas_call(
        functools.partial(_kernel, bits=bits, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // vpb, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, packed, scale)
