"""Sharded multi-host LM decode: the serving engine on a device mesh.

`serve.engine` runs prefill + slot-based continuous decode on one
device. This module places the same computation on a `launch.mesh`-style
mesh: parameters with `dist.sharding.param_specs` (FSDP rows / TP
columns), the decode KV/recurrent caches with `cache_specs` (batch over
`data`, KV heads over `model`), and the per-slot token/pos arrays with
`batch_specs` — all under the strict divisibility guard, so per-device
memory really is total/shards and never silently replicated. Prefill
and decode steps are jit-compiled with explicit in/out shardings; the
cache never leaves its placement between steps.

Why this is the throughput story: decode is memory-bound — each token
reads every (placed) parameter byte plus the slot's cache — so the
per-device byte footprint from the sharded avals *is* the modeled step
time, and tokens/s scales with devices exactly as those bytes shrink
(`benchmarks/decode_throughput.py` accounts it; `DecodePlan` exposes
the numbers).

Layers:

  * `plan_decode`     — specs + shardings + per-device byte accounting
                        for one (model, mesh, pool size), no allocation;
  * `compile_decode`  — jitted prefill/decode with explicit shardings;
  * `sharded_generate`— batched generate (one prefill + N decode steps),
                        the multi-device twin of `engine.generate`,
                        greedy or sampled under per-row folded keys;
  * `ShardedEngine`   — `engine.Engine` with every pool array pinned to
                        the mesh; slot admission (batched prefill +
                        scatter seating), EOS-on-first-token recycling
                        and per-request sampling keys are inherited, not
                        reimplemented — this class only compiles the
                        admission prefill/seat cells per admission width
                        with explicit shardings, so seating updates the
                        pool cache without it ever leaving the mesh.

On a data-only mesh the sharded pool is token-for-token identical to
the single-device engine (each device runs whole rows, same reduction
order); with a model axis, row-parallel contractions psum partial
products, so logits agree only to fp tolerance and greedy argmax can
flip on near-uniform (e.g. random-init) logits —
`tests/test_decode_multidevice.py` pins both contracts.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.dist import sharding as shd
from repro.models.api import Model
from repro.serve import seating
from repro.serve.engine import (
    Engine,
    _chunk_prefill_fn,
    _reject_enc_dec,
    request_key,
    sample_tokens,
)
from repro.serve.paging import PagingConfig, validate_page_size


def _decode_comm_budget(model: Model) -> dict:
    """Declared collective budget for this model's serve cells (the
    `repro.analysis` cell audit asserts the compiled inventory stays
    under it). Row/column-parallel TP contractions legitimately psum or
    gather a handful of partials per layer, and the scanned layer stack
    multiplies the loop body by its trip count — so the envelope scales
    with `n_layers`. What it catches is the SPMD blowup class: an
    accidental per-step resharding explodes the count far past
    O(layers)."""
    n = int(model.cfg.n_layers)
    per_layer_cap = 6 * n + 16
    return {
        "all-reduce": per_layer_cap,
        "all-gather": per_layer_cap,
        "reduce-scatter": per_layer_cap,
        "collective-permute": per_layer_cap,
        # XLA lowers some 2D-mesh reshards of the prefill activations
        # to all-to-all (measured: 2 on a 4x2 mesh at n_layers=2)
        "all-to-all": per_layer_cap,
    }


@dataclasses.dataclass(frozen=True)
class DecodePlan:
    """Placement plan for one (model, mesh, pool size): every sharding
    the decode path needs, plus per-device memory accounted from the
    sharded avals (what an allocator would reserve, with no allocation
    here)."""

    mesh: Mesh
    batch: int
    n_devices: int
    n_data: int  # combined data-axis size (pool rows per device = batch/n_data)
    params: Any  # NamedSharding pytree for the parameters
    cache: Any  # NamedSharding pytree for the decode cache
    token: NamedSharding  # (B,) arrays: tokens, pos, active masks
    logits: NamedSharding  # (B, V) decode/prefill logits
    prompts: NamedSharding  # (B, S) prefill token batch
    param_bytes_per_device: int
    cache_bytes_per_device: int
    param_bytes_total: int
    cache_bytes_total: int

    @property
    def cache_replication_factor(self) -> float:
        """1.0 = perfectly sharded; n_devices = fully replicated."""
        per_dev_if_perfect = self.cache_bytes_total / self.n_devices
        return self.cache_bytes_per_device / max(per_dev_if_perfect, 1)


def plan_decode(
    model: Model, params: Any, mesh: Mesh, *, batch_size: int,
    strict: bool = True, paging: Optional[PagingConfig] = None,
) -> DecodePlan:
    """Build the placement plan. `params` may be the real tree or its
    eval_shape aval tree — only shapes/dtypes are read. `strict=True`
    (the default) refuses a pool whose cache cannot shard its batch dim,
    instead of silently replicating it per device.

    With `paging`, the cache avals come from `model.init_cache_paged`:
    attention K/V leaves become (n_pages, page, ...) pools whose page
    axis sits exactly where the dense slot axis sat, so `cache_specs`
    shards pages over the data axes with the same rule — provided
    `n_pages` divides by the data-axis size (guarded here; the engine's
    `PageAllocator` then hands each slot pages from its own shard's
    contiguous range, which is the same contiguous split NamedSharding
    makes, so a slot's pages physically live with the slot)."""
    cfg = model.cfg
    axes = shd.data_axes(cfg, mesh)
    n_data = shd._axis_size(axes, mesh)
    n_dev = math.prod(mesh.devices.shape)
    if batch_size % max(n_data, 1):
        raise shd.ShardingGuardError(
            f"decode pool batch_size={batch_size} not divisible by the "
            f"mesh data axes {axes} (size {n_data})"
        )
    param_avals = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params
    )
    if (
        paging is not None
        and model.init_cache_paged is not None
        and validate_page_size(paging.page_size, model.attn_capacities())
    ):
        if paging.n_pages % max(n_data, 1):
            raise shd.ShardingGuardError(
                f"paged pool n_pages={paging.n_pages} not divisible by "
                f"the mesh data axes {axes} (size {n_data})"
            )
        cache_avals = jax.eval_shape(
            lambda: model.init_cache_paged(
                batch_size, paging.n_pages, paging.page_size
            )
        )
    else:
        cache_avals = jax.eval_shape(lambda: model.init_cache(batch_size))
    pspecs = shd.param_specs(param_avals, cfg, mesh)
    cspecs = shd.cache_specs(cache_avals, cfg, mesh, strict=strict)
    # slot token/pos and (B, V)/(B, S) batches share the batch rules —
    # the divisibility check above already guarantees strict passes
    bspecs = shd.batch_specs(
        {
            "token": jax.ShapeDtypeStruct((batch_size,), jnp.int32),
            "row": jax.ShapeDtypeStruct((batch_size, 1), jnp.int32),
        },
        cfg, mesh, strict=strict,
    )
    replicated = jax.tree.map(lambda s: P(*([None] * len(s))), cspecs,
                              is_leaf=lambda s: isinstance(s, P))
    return DecodePlan(
        mesh=mesh,
        batch=batch_size,
        n_devices=n_dev,
        n_data=n_data,
        params=shd.named(pspecs, mesh),
        cache=shd.named(cspecs, mesh),
        token=NamedSharding(mesh, bspecs["token"]),
        logits=NamedSharding(mesh, bspecs["row"]),
        prompts=NamedSharding(mesh, bspecs["row"]),
        param_bytes_per_device=shd.bytes_per_device(
            param_avals, pspecs, mesh
        ),
        cache_bytes_per_device=shd.bytes_per_device(
            cache_avals, cspecs, mesh
        ),
        param_bytes_total=shd.bytes_per_device(
            param_avals,
            jax.tree.map(lambda s: P(*([None] * len(s))), pspecs,
                         is_leaf=lambda s: isinstance(s, P)),
            mesh,
        ),
        cache_bytes_total=shd.bytes_per_device(
            cache_avals, replicated, mesh
        ),
    )


def compile_decode(
    model: Model, plan: DecodePlan
) -> Tuple[Callable, Callable]:
    """(prefill, decode_step) jit-compiled with explicit in/out
    shardings from `plan`. The cache argument/result keeps the
    `cache_specs` placement across every step, so decode never migrates
    the pool's persistent state."""
    _reject_enc_dec(model.cfg, "sharded decode (compile_decode)")
    prefill = jax.jit(
        model.prefill,
        in_shardings=(plan.params, plan.prompts),
        out_shardings=(plan.logits, plan.cache),
    )
    decode = jax.jit(
        model.decode_step,
        in_shardings=(plan.params, plan.cache, plan.token, plan.token),
        out_shardings=(plan.logits, plan.cache),
    )
    return prefill, decode


def place_params(params: Any, plan: DecodePlan) -> Any:
    return jax.device_put(params, plan.params)


def init_placed_params(model: Model, key: jax.Array, mesh: Mesh) -> Any:
    """`model.init(key)` created directly in `plan_decode`'s parameter
    placement, so no device ever holds the whole tree (a published-width
    model does not fit one device)."""
    avals = jax.eval_shape(model.init, key)
    shardings = shd.named(shd.param_specs(avals, model.cfg, mesh), mesh)
    return jax.jit(model.init, out_shardings=shardings)(key)


def sharded_generate(
    model: Model,
    params: Any,
    prompts: jax.Array,  # (B, S) int32 — same-length batch
    *,
    mesh: Mesh,
    max_new: int,
    params_placed: bool = False,
    plan: Optional[DecodePlan] = None,
    greedy: bool = True,
    key: Optional[jax.Array] = None,
    temperature: float = 1.0,
    top_k: int = 0,
) -> jax.Array:
    """Multi-device `engine.generate`: one sharded prefill + `max_new`
    sharded decode steps. Returns (B, max_new) int32.

    Greedy by default; with `greedy=False` and a `key`, row b's token t
    is drawn with `fold_in(fold_in(key, b), t)` — `engine.generate`'s
    schedule, so the two paths stay stream-identical wherever their
    logits do (data-only meshes; a model axis psums partial products,
    which can flip samples only to fp tolerance)."""
    b, s = prompts.shape
    if plan is None:
        plan = plan_decode(model, params, mesh, batch_size=b)
    if plan.batch != b:
        raise ValueError(f"plan batch {plan.batch} != prompts batch {b}")
    prefill, decode = compile_decode(model, plan)
    if not params_placed:
        params = place_params(params, plan)
    prompts = jax.device_put(
        jnp.asarray(prompts, jnp.int32), plan.prompts
    )
    sampling = not greedy and key is not None
    if sampling:
        row_keys = jax.vmap(lambda r: request_key(key, r))(jnp.arange(b))
        draw = lambda lg, t: sample_tokens(
            lg, jax.vmap(jax.random.fold_in)(
                row_keys, jnp.full((b,), t, jnp.int32)
            ),
            temperature=temperature, top_k=top_k,
        )
    last_logits, cache = prefill(params, prompts)
    outs = []
    tok = draw(last_logits, 0) if sampling else jnp.argmax(
        last_logits, axis=-1
    ).astype(jnp.int32)
    for t in range(max_new):
        outs.append(tok)
        pos = jax.device_put(
            jnp.full((b,), s + t, jnp.int32), plan.token
        )
        logits, cache = decode(
            params, cache, jax.device_put(tok, plan.token), pos
        )
        tok = draw(logits, t + 1) if sampling else jnp.argmax(
            logits, axis=-1
        ).astype(jnp.int32)
    return jnp.stack(outs, axis=1)


class ShardedEngine(Engine):
    """The slot engine with its pool pinned to a mesh.

    Everything behavioral — batched prefill admission, scatter seating,
    EOS-on-first-token slot recycling, per-request sampling keys — is
    inherited from `Engine`; this class only overrides *where arrays
    live and how cells compile*: params/cache/slot-state are device_put
    to the plan's shardings at init, the jitted decode carries explicit
    in/out shardings so the cache round-trips without migrating, and
    each admission width gets a (prefill, seat) cell pair compiled with
    explicit shardings — the prefill cell's cache rows come out in the
    admission-plan placement and `seating.scatter_slots` writes them
    into the pool under `out_shardings=plan.cache`, so seating never
    unshards the pool. Admission widths are padded to the mesh data-axis
    multiple (`_admission_rows`); pad rows repeat a real prompt and
    their outputs are discarded. Host-side `.at[].set` slot updates
    preserve the committed sharding; the step wrapper re-pins token/pos
    anyway (jit with explicit in_shardings rejects, rather than
    reshards, mismatched committed arrays)."""

    def __init__(self, model: Model, params: Any, *, batch_size: int,
                 mesh: Mesh, greedy: bool = True, strict: bool = True,
                 temperature: float = 1.0, top_k: int = 0,
                 key: Optional[jax.Array] = None,
                 paging: Optional[PagingConfig] = None,
                 chunk_tokens: Optional[int] = None):
        # the plan must exist before Engine.__init__ runs the hooks
        self.mesh = mesh
        self._strict = strict
        self.plan = plan_decode(
            model, params, mesh, batch_size=batch_size, strict=strict,
            paging=paging,
        )
        self._adm_cells: dict[int, tuple] = {}
        self._chunk_cells: dict[int, tuple] = {}
        super().__init__(
            model, params, batch_size=batch_size, greedy=greedy,
            temperature=temperature, top_k=top_k, key=key,
            paging=paging, chunk_tokens=chunk_tokens,
        )

    def _place_params(self, params: Any) -> Any:
        return jax.device_put(params, self.plan.params)

    def _place_cache(self, cache: Any) -> Any:
        return jax.device_put(cache, self.plan.cache)

    def _place_batch(self, x: jax.Array) -> jax.Array:
        return jax.device_put(x, self.plan.token)

    def _place_tbl(self, x: jax.Array) -> jax.Array:
        # (B, span) indirection rows shard with the slots they describe
        return jax.device_put(x, self.plan.prompts)

    def _paging_shards(self) -> int:
        return max(self.plan.n_data, 1)

    def _compile_decode(self) -> Callable:
        plan = self.plan
        if self._pg is not None:
            model, page = self.model, self._page
            cell = obs.get().probe.track(
                "serve.decode_step",
                jax.jit(
                    lambda p, c, t, pos, tbl: model.decode_step_paged(
                        p, c, t, pos, tbl, page
                    ),
                    in_shardings=(
                        plan.params, plan.cache, plan.token, plan.token,
                        plan.prompts,
                    ),
                    out_shardings=(plan.logits, plan.cache),
                ),
                budget=_decode_comm_budget(self.model),
                sharded_outputs=True,
            )

            def pstep(params, cache, tok, pos):
                return cell(
                    params, cache,
                    jax.device_put(tok, plan.token),
                    jax.device_put(pos, plan.token),
                    self._tbl_device(),
                )

            return pstep
        _, decode = compile_decode(self.model, plan)
        decode = obs.get().probe.track(
            "serve.decode_step", decode,
            budget=_decode_comm_budget(self.model),
            sharded_outputs=True,
        )

        def step(params, cache, tok, pos):
            return decode(
                params, cache,
                jax.device_put(tok, plan.token),
                jax.device_put(pos, plan.token),
            )

        return step

    def _admission_rows(self, n: int) -> int:
        # the admission prefill is itself a sharded cell: its batch dim
        # must divide over the mesh data axes (strict guard), so pad up
        return n + (-n) % max(self.plan.n_data, 1)

    def _admit_span_attrs(self) -> dict:
        # seen in the trace: which mesh this admission ran on, so a
        # lineage join can attribute seating cost per (mesh, width)
        return {
            "mesh": "x".join(str(d) for d in self.mesh.devices.shape),
            "n_data": int(self.plan.n_data),
        }

    def _admission_cell(self, rows: int):
        cell = self._adm_cells.get(rows)
        if cell is None:
            rplan = plan_decode(
                self.model, self.params, self.mesh, batch_size=rows,
                strict=self._strict,
            )
            probe = obs.get().probe
            prefill = probe.track(
                f"serve.prefill.w{rows}",
                jax.jit(
                    self.model.prefill,
                    in_shardings=(self.plan.params, rplan.prompts),
                    out_shardings=(rplan.logits, rplan.cache),
                ),
                budget=_decode_comm_budget(self.model),
                sharded_outputs=True,
            )
            if self._pg is not None:
                # admission rows stay a dense cache (what prefill
                # emits); seating splits their K/V rows into pages and
                # lands each on its mapped physical page in the pool
                seat = probe.track(
                    f"serve.seat.w{rows}",
                    jax.jit(
                        functools.partial(
                            seating.scatter_pages, layouts=self._layouts
                        ),
                        in_shardings=(
                            self.plan.cache, rplan.cache, None, None,
                            None,
                        ),
                        out_shardings=self.plan.cache,
                        donate_argnums=0,
                    ),
                    donate=(0,), sharded_outputs=True,
                )
            else:
                seat = probe.track(
                    f"serve.seat.w{rows}",
                    jax.jit(
                        seating.scatter_slots,
                        in_shardings=(
                            self.plan.cache, rplan.cache, None, None
                        ),
                        out_shardings=self.plan.cache,
                        donate_argnums=0,
                    ),
                    donate=(0,), sharded_outputs=True,
                )
            place = lambda p: jax.device_put(
                jnp.asarray(p, jnp.int32), rplan.prompts
            )
            cell = (prefill, seat, place)
            self._adm_cells[rows] = cell
        return cell

    def _chunk_cell(self, c: int, rows: int):
        """Per-chunk-width cell with explicit shardings: the chunk
        cache is a dense rows cache on the admission-width plan; token
        and position chunks shard like prompt batches. One compiled
        cell per width (`serve.chunk.c{c}`), warm after first use."""
        cell = self._chunk_cells.get(c)
        if cell is None:
            rplan = plan_decode(
                self.model, self.params, self.mesh, batch_size=rows,
                strict=self._strict,
            )
            step = obs.get().probe.track(
                f"serve.chunk.c{c}",
                jax.jit(
                    _chunk_prefill_fn(self.model),
                    in_shardings=(
                        self.plan.params, rplan.cache, rplan.prompts,
                        rplan.prompts, None, None,
                    ),
                    out_shardings=(rplan.logits, rplan.cache),
                ),
                budget=_decode_comm_budget(self.model),
                sharded_outputs=True,
            )
            init_rows = lambda: jax.device_put(
                self.model.init_cache(rows), rplan.cache
            )
            place = lambda x: jax.device_put(
                jnp.asarray(x, jnp.int32), rplan.prompts
            )
            cell = (step, init_rows, place)
            self._chunk_cells[c] = cell
        return cell

    @property
    def n_devices(self) -> int:
        return self.plan.n_devices

    @property
    def cache_bytes_per_device(self) -> int:
        return self.plan.cache_bytes_per_device

    @property
    def param_bytes_per_device(self) -> int:
        return self.plan.param_bytes_per_device
