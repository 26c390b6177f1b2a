"""Jitted bucketed batched inference over the compiled accelerator
program, sharded along the mesh `data` axis.

One `FleetRunner` owns one jitted classify function; it retraces exactly
once per declared bucket shape (the scheduler guarantees no other shape
ever arrives — `jit_cache_misses()` exposes the count so tests can
assert no silent recompiles). Batches are sharded over the mesh's data
axes with `dist.sharding.batch_specs`, so on an N-device mesh each
device classifies bucket/N patients — the software model of N accelerator
chips monitoring disjoint slices of the fleet.

Compute paths:

  * ``twin``      — the default fleet path: the compiled program's
    sparse-quantized weights are decompressed once at init into the
    dequantized dense conv form and run through XLA's conv. Numerically
    this is `spe_matmul(..., path="dense")` per layer — the same
    weights the chip stores — but at XLA conv throughput.
  * ``reference`` / ``kernel`` / ``dense`` — `compiler.execute`'s
    per-layer im2col dataflow (the chip's SPad streaming order), for
    cross-path agreement checks and chip-faithful execution.

Whatever the path, *time* accounting is the chip's: every segment costs
`program.report.latency_s` on its device's chip twin, so per-patient
latency and modeled fleet throughput always reflect the silicon.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro import obs
from repro.core import compiler, sparsity, vadetect
from repro.dist import sharding as shd


@dataclasses.dataclass(frozen=True)
class _FleetShardCfg:
    """Shim profile for `dist.sharding`: the VA fleet is pure data
    parallelism — no tensor parallelism, params replicated."""

    use_tp: bool = False
    fsdp: bool = False


def twin_weights(program: compiler.AcceleratorProgram) -> list[dict]:
    """Decompress the program's layers into dequantized dense conv
    weights (ks, c_in, c_out) — bit-identical to what `spe_matmul`'s
    "dense" path contracts against."""
    out = []
    for m in program.layer_meta:
        layer = program.layers[m["name"]]
        ks, c_in, c_out = m["ksize"], m["c_in"], m["c_out"]
        vals = layer.values_q.astype(jnp.float32)
        if layer.sparse:
            dense = sparsity.decompress(
                vals,
                layer.select,
                sparsity.SparsityConfig(layer.group_size, layer.keep),
                layer.k_dense,
            )
        else:
            dense = vals
        w = (dense * layer.scale)[: ks * c_in].reshape(ks, c_in, c_out)
        out.append({"w": w, "b": program.biases[m["name"]]})
    return out


def _twin_logits(
    weights: list[dict], meta: list[dict], x: jax.Array
) -> jax.Array:
    """(B, 512) -> (B, 2) logits through the decompressed conv twin."""
    if x.ndim == 2:
        x = x[..., None]
    c = x.shape[-1]
    if c < vadetect.N_INPUT_PAD:
        x = jnp.pad(
            x, ((0, 0), (0, 0), (0, vadetect.N_INPUT_PAD - c))
        )
    h = x
    n = len(meta)
    for i, (m, wb) in enumerate(zip(meta, weights)):
        y = jax.lax.conv_general_dilated(
            h,
            wb["w"],
            window_strides=(m["stride"],),
            padding="SAME",
            dimension_numbers=("NWC", "WIO", "NWC"),
        ) + wb["b"]
        h = jax.nn.relu(y) if i < n - 1 else y
    return jnp.mean(h, axis=1)


class FleetRunner:
    """Fixed-shape batched classifier over one compiled program."""

    def __init__(
        self,
        program: compiler.AcceleratorProgram,
        cfg: vadetect.VAConfig = vadetect.VAConfig(),
        *,
        path: str = "twin",
        mesh: Optional[Mesh] = None,
    ):
        self.program = program
        self.cfg = cfg
        self.path = path
        self.mesh = mesh
        if path == "twin":
            weights = twin_weights(program)
            meta = program.layer_meta
            logits_fn = lambda x: _twin_logits(weights, meta, x)
        else:
            logits_fn = lambda x: compiler.execute(
                program, x, cfg, path=path
            )
        self._infer = obs.get().probe.track(
            f"stream.classify.{path}",
            jax.jit(
                lambda x: jnp.argmax(logits_fn(x), axis=-1).astype(
                    jnp.int32
                )
            ),
        )
        if mesh is not None:
            spec = shd.batch_specs(
                {"x": jax.ShapeDtypeStruct((0, 0), jnp.float32)},
                _FleetShardCfg(),
                mesh,
            )["x"]
            self._in_sharding = jax.sharding.NamedSharding(mesh, spec)
        else:
            self._in_sharding = None

    # -- execution ----------------------------------------------------------

    def classify(self, signals: jax.Array) -> jax.Array:
        """(bucket, 512) f32 -> (bucket,) i32 predictions. The batch dim
        is sharded over the mesh data axes when a mesh is attached."""
        if self._in_sharding is not None:
            if signals.shape[0] % max(1, self.n_devices):
                # silently falling back to one device would void the
                # "N chip twins over disjoint fleet slices" contract —
                # declare divisible bucket shapes instead
                raise ValueError(
                    f"bucket {signals.shape[0]} not divisible by "
                    f"{self.n_devices} mesh devices"
                )
            signals = jax.device_put(signals, self._in_sharding)
        return self._infer(signals)

    # -- accounting ---------------------------------------------------------

    @property
    def n_devices(self) -> int:
        if self.mesh is None:
            return 1
        return math.prod(self.mesh.devices.shape)

    @property
    def chip_latency_s(self) -> float:
        """Modeled silicon latency of one segment inference (35 µs at
        the paper's operating point)."""
        return self.program.report.latency_s

    def batch_service_s(self, bucket: int) -> float:
        """Modeled fleet service time of one packed bucket: each device's
        chip twin runs its shard of ceil(bucket/N) segments serially
        (padding rows occupy chip time — the shape is fixed)."""
        per_dev = -(-bucket // max(1, self.n_devices))
        return per_dev * self.chip_latency_s

    def modeled_segments_per_s(self) -> float:
        """Aggregate modeled chip-fleet throughput (N chips, saturated)."""
        return self.n_devices / self.chip_latency_s

    def jit_cache_misses(self) -> int:
        """Compiled-variant count of the classify function — equals the
        number of distinct batch shapes ever seen. The scheduler's
        pad-to-bucket contract keeps this at len(buckets)."""
        return int(self._infer._cache_size())
