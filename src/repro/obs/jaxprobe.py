"""JAX-specific telemetry probes: recompiles, step timing, memory.

Three concerns the generic registry/tracer can't see:

  * **Compile/recompile visibility** — every jitted cell the serving
    and training paths compile (decode step, per-width admission
    prefills, stream bucket classify, multipod reduction stages) is
    registered here by name; `cache_sizes()` reads each cell's jit
    cache entry count (`_cache_size`), so "did anything retrace after
    warmup" is one snapshot diff (`new_misses`). This generalizes the
    PR 2 stream-only miss-count check to every compiled cell —
    `tests/test_obs.py` guards both stream buckets and decode
    admission widths with it.
  * **Bounded step timing** — `timed_call` wraps a jitted call in
    `block_until_ready` so the observed duration is device work, not
    dispatch; only used when telemetry is enabled (callers pass the
    enabled flag), so the async pipeline is never serialized silently.
  * **Device memory gauges** — `device_memory_bytes()` prefers the
    platform allocator's `memory_stats()["bytes_in_use"]` and falls
    back to summing `jax.live_arrays()` (the only option on forced
    host-platform devices); `observe_memory` folds it into live/peak
    gauges.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import numpy as np


def jit_cache_size(fn) -> Optional[int]:
    """Compiled-variant count of a jitted callable (None if the
    installed jax doesn't expose it or `fn` isn't a jit wrapper)."""
    try:
        return int(fn._cache_size())
    except Exception:  # noqa: BLE001 — probe must never raise
        return None


@dataclasses.dataclass
class CellInfo:
    """Audit metadata for one tracked jit cell.

    `budget` is the cell's declared collective comm budget: HLO
    collective op name -> max occurrences in the optimized module
    (`None` means "undeclared" and the auditor treats it as the empty
    budget — no collectives allowed — so single-device cells need no
    declaration and sharded cells must state theirs). `donate` mirrors
    the jit's `donate_argnums`; the auditor uses it to assert no
    donation was silently dropped. `sharded_outputs` declares that at
    least one output must land sharded (not fully replicated).
    `call_avals` is the (args, kwargs) aval pytree captured from the
    cell's first real call — what the auditor re-lowers with."""

    name: str
    fn: Callable
    budget: Optional[dict] = None
    donate: tuple = ()
    sharded_outputs: bool = False
    call_avals: Optional[tuple] = None


def _aval_of(x):
    """Abstract one call-argument leaf: arrays become
    `ShapeDtypeStruct` (keeping a `NamedSharding` so sharded cells
    re-lower on their mesh; single-device placements stay abstract),
    everything else passes through verbatim so weak-typed Python
    scalars retrace exactly as the real call did. Never holds a buffer
    reference — safe to capture args that are about to be donated."""
    if isinstance(x, jax.Array):
        sh = x.sharding
        if isinstance(sh, jax.sharding.NamedSharding):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    if isinstance(x, np.ndarray):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


class TrackedCell:
    """Transparent wrapper an enabled probe returns from `track`:
    records the argument avals of the first call into the cell's
    `CellInfo` (one tree_map, then a plain delegate — far inside the
    disabled-telemetry overhead budget), and forwards attribute access
    to the underlying jit wrapper so `.lower`/`._cache_size` callers
    are unaffected."""

    def __init__(self, info: CellInfo):
        self._info = info
        self._fn = info.fn

    def __call__(self, *args, **kwargs):
        if self._info.call_avals is None:
            self._info.call_avals = jax.tree.map(
                _aval_of, (args, kwargs)
            )
        return self._fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)


class JitProbe:
    """Named registry of jitted cells for recompile accounting.

    A disabled probe drops registrations (no strong refs pinning jit
    caches alive through a long test session); an enabled one keeps
    them for the lifetime of the run — benchmark/launcher scale."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._cells: dict[str, CellInfo] = {}

    def track(self, name: str, fn, *, budget: Optional[dict] = None,
              donate: tuple = (), sharded_outputs: bool = False):
        """Register `fn` under `name` (idempotent; later registrations
        under the same name win — e.g. a rebuilt engine). Returns a
        call-through `TrackedCell` when enabled (disabled probes return
        `fn` unchanged) so call sites can wrap in place. Keyword
        metadata feeds the `repro.analysis` cell auditor."""
        if not self.enabled:
            return fn
        info = CellInfo(
            name=name, fn=fn, budget=budget, donate=tuple(donate),
            sharded_outputs=sharded_outputs,
        )
        self._cells[name] = info
        return TrackedCell(info)

    def cells(self) -> dict:
        """name -> `CellInfo` for every tracked cell (the
        `repro.analysis.cellaudit` walk surface)."""
        return dict(sorted(self._cells.items()))

    def cache_sizes(self) -> dict:
        """name -> compiled-variant count for every tracked cell (the
        BENCH `telemetry.recompiles` section)."""
        return {
            name: jit_cache_size(info.fn)
            for name, info in sorted(self._cells.items())
        }

    def snapshot(self) -> dict:
        return self.cache_sizes()

    def new_misses(self, since: dict) -> dict:
        """Cells that compiled new variants after `since` (a
        `snapshot()`), name -> extra compile count. Empty means zero
        recompiles — the regression-guard condition."""
        out = {}
        for name, n in self.cache_sizes().items():
            before = since.get(name)
            if n is not None and before is not None and n > before:
                out[name] = n - before
        return out


class _NullProbe:
    __slots__ = ()
    enabled = False

    def track(self, name, fn, **meta):
        return fn

    def cells(self):
        return {}

    def cache_sizes(self):
        return {}

    snapshot = cache_sizes

    def new_misses(self, since):
        return {}


NULL_PROBE = _NullProbe()


# ---------------------------------------------------------------------------
# timing / memory
# ---------------------------------------------------------------------------


def timed_call(histogram, fn, *args, **kwargs):
    """Call `fn`, block until its result is ready, and observe the
    bounded duration into `histogram` (a registry histogram or the
    null one). Returns the (ready) result."""
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    histogram.observe(time.perf_counter() - t0)
    return out


def device_memory_bytes() -> int:
    """Best-effort live device memory: allocator stats when the
    platform reports them, else the sum of live jax array bytes (the
    forced-host-device fallback; it misses internal allocator slack but
    tracks the arrays the program actually holds)."""
    total = 0
    saw_stats = False
    for d in jax.devices():
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001
            stats = None
        if stats and "bytes_in_use" in stats:
            total += int(stats["bytes_in_use"])
            saw_stats = True
    if saw_stats:
        return total
    return int(sum(a.nbytes for a in jax.live_arrays()))


def observe_memory(registry) -> int:
    """Sample device memory into the live/peak gauges; returns the
    sampled byte count. The `jax.device_bytes` gauge's `.peak` is the
    BENCH `telemetry.peak_device_memory_bytes` value."""
    n = device_memory_bytes()
    registry.gauge("jax.device_bytes").set(n)
    return n


def cost_gauges(registry, name: str, compiled) -> dict:
    """Fold a compiled cell's `cost_analysis` flops/bytes estimates
    into gauges (`<name>.flops`, `<name>.bytes_accessed`); returns the
    cost dict."""
    ca = compiled.cost_analysis()
    if "flops" in ca:
        registry.gauge(f"{name}.flops").set(float(ca["flops"]))
    if "bytes accessed" in ca:
        registry.gauge(f"{name}.bytes_accessed").set(
            float(ca["bytes accessed"])
        )
    return ca
