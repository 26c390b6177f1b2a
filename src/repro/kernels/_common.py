"""Shared helpers for the Pallas TPU kernels.

Everything here runs *inside* kernel bodies (on VMEM-resident tiles) or is
shape plumbing for the ops wrappers. Block shapes default to MXU-aligned
(128 multiples); the working set per grid cell is kept well under the
~16 MB/core VMEM budget of TPU v5e (see each kernel's header math).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    """Zero-pad `axis` of `x` up to the next multiple of `multiple`."""
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def unpack_tile(packed: jax.Array, bits: int) -> jax.Array:
    """VMEM unpack: uint8 (Kp, N) tile -> signed int32 (Kp*vpb, N) tile.

    Mirrors `core.quant.unpack_planes` but with static shapes only (no
    slicing to a dynamic K — the wrapper pre-pads K to tile multiples).
    1-bit planes decode {0,1} -> {-1,+1}.
    """
    vpb = 8 // bits
    mask = (1 << bits) - 1
    kp, n = packed.shape
    shifts = (jnp.arange(vpb, dtype=jnp.int32) * bits).reshape(1, vpb, 1)
    u = (packed.astype(jnp.int32)[:, None, :] >> shifts) & mask
    u = u.reshape(kp * vpb, n)
    if bits == 1:
        return jnp.where(u > 0, 1, -1)
    sign_bit = 1 << (bits - 1)
    return jnp.where(u >= sign_bit, u - (1 << bits), u)


def decompress_tile(
    values: jax.Array, select: jax.Array, group_size: int, keep: int
) -> jax.Array:
    """VMEM decompress: (Kk, N) values+select -> dense (Kk//keep*G, N).

    Gather-free (TPU VPU-friendly): a one-hot compare against an in-group
    iota scatters each compressed row into its dense slot. Cost is
    keep * dense_K * N compares — ~keep/G of the matmul's MACs, i.e. noise
    next to the MXU work it unlocks.
    """
    kk, n = values.shape
    groups = kk // keep
    vals = values.reshape(groups, keep, n).astype(jnp.float32)
    sel = select.reshape(groups, keep, n).astype(jnp.int32)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, group_size, 1, 1), 1)
    onehot = (sel[:, None, :, :] == slot).astype(jnp.float32)
    dense = jnp.sum(onehot * vals[:, None, :, :], axis=2)  # (groups, G, N)
    return dense.reshape(groups * group_size, n)


def flatten_batch(x: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    """(..., K) -> ((M, K), leading_shape) for 2-D kernel entry."""
    lead = x.shape[:-1]
    m = 1
    for s in lead:
        m *= s
    return x.reshape(m, x.shape[-1]), lead
