"""Batched serving engine: batched prefill admission + slot decode.

A fixed pool of `batch_size` decode slots runs one jitted `decode_step`
per tick for the whole pool (decode is memory-bound: batching the pool
amortizes the weight reads — exactly the roofline term the paper's
compressed weights attack). Admission runs the real batched
`model.prefill` over the requests being seated (grouped by prompt
length) and scatters the resulting per-request cache rows into the
placed pool via `serve.seating` — O(prompt) work per request,
independent of the pool size. Because seating overwrites a slot's
entire cache row, it is exact for attention KV *and* step-advancing
recurrent (rg-lru / rwkv) caches alike; finished slots (EOS or
max_tokens) are recycled.

Sampling: greedy argmax by default; with `greedy=False` every request
draws through `sample_tokens` (temperature / top-k) under a per-request
folded PRNG key — token t of request `uid` uses
`fold_in(fold_in(key, uid), t)`, so streams are reproducible across
runs and invariant to seat order and co-tenancy. `generate` follows the
same schedule (row index as uid), making the two paths token-identical
under sampling as well as greedy.

Weight-only quantization (`quantize_for_serving`) converts dense params
to the packed mixed-bit-width format; the model's `linear_apply`
dispatches on the format, so the same jitted decode_step serves both.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.concurrency import driver_thread_only

from repro import obs
from repro.dist import sharding as shd
from repro.models.api import Model
from repro.models.layers import compile_linear_quant
from repro.serve import seating
from repro.serve.paging import (
    PageAllocator,
    PagesExhaustedError,
    PagingConfig,
    pages_for_position,
    validate_page_size,
)

# param-path leaf dirs that stay dense at serve time (numerically
# sensitive or tiny): embeddings, router, norms, rwkv adapters
_QUANT_TARGETS = (
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    "w_r", "w_k", "w_v", "w_g", "w_o", "cm_k", "cm_v", "cm_r",
    "w_x", "w_out",
)


class EncDecUnsupportedError(TypeError):
    """An encoder-decoder (whisper-family) model hit a decoder-only
    serving path. These models need a frames-aware prefill (the open
    ROADMAP "Enc-dec prefill" item); until that lands, drive them
    directly through `model.prefill(params, tokens, frames)` +
    `model.decode_step` (see `tests/test_serve.py::
    test_decode_matches_teacher_forced` for the pattern)."""


def _reject_enc_dec(cfg, where: str) -> None:
    if cfg.is_enc_dec:
        raise EncDecUnsupportedError(
            f"{where} drives the decoder-only path, but {cfg.name!r} is "
            f"an encoder-decoder model: its prefill needs audio frames "
            f"(frames-aware prefill is not wired yet — ROADMAP 'Enc-dec "
            f"prefill'). Run it through model.prefill(params, tokens, "
            f"frames) + model.decode_step directly instead."
        )


def quantize_for_serving(params: Any, bits: int = 8) -> Any:
    """Dense master params -> packed mixed-bit-width serving params."""

    def visit(tree, name=""):
        if isinstance(tree, dict):
            if "w" in tree and isinstance(tree["w"], jax.Array) and (
                name in _QUANT_TARGETS and tree["w"].ndim in (2, 3)
            ):
                return compile_linear_quant(tree, bits)
            return {k: visit(v, k) for k, v in tree.items()}
        return tree

    return visit(params)


def sample_tokens(
    logits: jax.Array,  # (B, V) float
    keys: jax.Array,  # (B, 2) uint32 — one PRNG key per row
    *,
    temperature: float = 1.0,
    top_k: int = 0,
) -> jax.Array:
    """Per-row temperature / top-k sampling. Returns (B,) int32.

    `top_k <= 0` or `top_k >= V` samples the full distribution. The
    top-k mask keeps every logit >= the k-th largest, so ties at the
    threshold are all eligible (deterministic given the key, never
    index-order-dependent). `temperature <= 0` degenerates to greedy
    argmax over the masked logits — identical to plain argmax, since
    masking only removes non-argmax entries.
    """
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    if top_k and top_k < v:
        thresh = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < thresh, -jnp.inf, logits)
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / float(temperature)
    return jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)


def request_key(base: jax.Array, uid: int) -> jax.Array:
    """Per-request PRNG key: fold the request uid into the engine/run
    key. Token t then folds t into this — the schedule both the engine
    and `generate` follow, so sampled streams match across paths and
    are invariant to seat order."""
    return jax.random.fold_in(base, uid)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: jax.Array  # (S,) int32
    max_new: int = 32
    eos: Optional[int] = None
    # filled by the engine:
    output: list = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class _ChunkState:
    """One long prompt mid chunked-prefill: a standalone rows-cache the
    chunk cell advances `chunk_tokens` prompt tokens per tick, so the
    pool's decode ticks (and other admissions) interleave instead of
    stalling behind one O(prompt) prefill. Takes a pool slot only at
    completion."""

    req: Request
    cache: Any  # rows-format cache being built
    done: int  # prompt tokens already fed
    shard: int  # page-pool shard the reservation (and seat) lives on
    logits: Any = None  # (rows, V) final-chunk logits once ready
    ready: bool = False


def _chunk_prefill_fn(model: Model) -> Callable:
    """Chunked-prefill cell body: scan `decode_step` over one chunk of
    prompt tokens. Pad steps (`act[t]` False) re-feed the last real
    (token, position) but their cache writes are masked out, so the
    returned cache is exactly the real prefix's; `last_idx` selects the
    last real step's logits (recurrent blocks advance on pad steps, so
    the final scan slot is not always the right one)."""

    def fn(params, cache, toks, poss, act, last_idx):
        # toks/poss (rows, c) int32; act (c,) bool; last_idx () int32
        def body(cache, xs):
            tok_t, pos_t, a_t = xs
            logits, nc = model.decode_step(params, cache, tok_t, pos_t)
            cache = jax.tree.map(
                lambda old, new: jnp.where(a_t, new, old), cache, nc
            )
            return cache, logits

        cache, logits = jax.lax.scan(
            body, cache, (toks.T, poss.T, act)
        )
        return jnp.take(logits, last_idx, axis=0), cache

    return fn


class Engine:
    """Slot-based batched decoder around a Model.

    Array placement and compilation go through overridable hooks
    (`_place_params` / `_place_cache` / `_place_batch` /
    `_compile_decode` / `_admission_cell` / `_admission_rows`) so
    `serve.sharded.ShardedEngine` can pin every pool array — and the
    admission prefill/seating cells — to a device mesh while inheriting
    the slot semantics (admission, EOS-on-first-token, recycling)
    unchanged.

    Admission is batched: each round takes up to |free slots| queued
    requests, groups them by prompt length, runs one `model.prefill`
    per group, and scatter-seats the resulting cache rows into the
    pool (`serve.seating.scatter_slots`). Work is O(prompt) per
    request, independent of pool size — `admission_rowsteps` counts
    the (row x token) units actually spent, which
    `benchmarks/decode_throughput.py` asserts pool-size-independent.
    """

    def __init__(self, model: Model, params: Any, *, batch_size: int,
                 greedy: bool = True, temperature: float = 1.0,
                 top_k: int = 0, key: Optional[jax.Array] = None,
                 paging: Optional[PagingConfig] = None,
                 chunk_tokens: Optional[int] = None):
        _reject_enc_dec(model.cfg, "the slot engine")
        self.model = model
        self.params = self._place_params(params)
        self.batch = batch_size
        self.greedy = greedy
        self.temperature = temperature
        self.top_k = top_k
        self.key = key if key is not None else jax.random.PRNGKey(0)
        if chunk_tokens is not None and chunk_tokens < 1:
            raise ValueError(f"chunk_tokens must be >= 1, got {chunk_tokens}")
        self.chunk_tokens = chunk_tokens
        self.paging = paging
        self._pg: Optional[PageAllocator] = None
        self._page = 0
        self._span = 0
        self._layouts: dict = {}
        if paging is not None:
            if model.init_cache_paged is None:
                raise TypeError(
                    f"model {model.cfg.name!r} has no paged cache support"
                )
            self._page = paging.page_size
            self._span = validate_page_size(
                paging.page_size, model.attn_capacities()
            )
            if self._span:
                # pure-recurrent models have nothing to page: the paged
                # cache degenerates to the dense pool and no allocator
                # is needed (span == 0 keeps _pg None)
                self._layouts = model.page_layouts(paging.page_size)
                self._pg = PageAllocator(
                    paging.n_pages, self._paging_shards()
                )
                if batch_size % self._paging_shards():
                    raise shd.ShardingGuardError(
                        f"batch_size={batch_size} not divisible by "
                        f"{self._paging_shards()} page-pool shards"
                    )
        # host-authoritative slot->page indirection table + per-slot
        # mirrors (page count, last written position). The device only
        # ever sees a snapshot of _tbl, passed into the decode cell per
        # tick — never stored in the cache pytree.
        if self._pg is not None:
            self._tbl = np.stack([
                np.full((self._span,),
                        self._pg.scratch(self._slot_shard(i)), np.int32)
                for i in range(batch_size)
            ])
            self._npages = [0] * batch_size
            self._hpos = [0] * batch_size
        self._decode = self._compile_decode()
        self._queue: list[Request] = []
        self._slots: list[Optional[Request]] = [None] * batch_size
        self._chunks: list[_ChunkState] = []
        self._chunk_wait: list[Request] = []
        self.cache = self._place_cache(self._init_cache())
        zi = lambda: self._place_batch(jnp.zeros((batch_size,), jnp.int32))
        self.pos = zi()
        self.tokens = zi()
        self.active = self._place_batch(jnp.zeros((batch_size,), bool))
        # compatibility shim: last (token, pos) fed to each slot by the
        # pool decode. `tokens`/`pos` hold the *pending* decode input;
        # inactive slots re-feed their last-fed state each tick (an
        # idempotent rewrite for attention caches, and harmless for
        # recurrent ones — an unseated row's state is dead weight that
        # scatter seating fully overwrites at the next admission).
        self._ctok = zi()
        self._cpos = zi()
        # sampling state: per-slot folded request keys + #tokens already
        # generated (the fold index for the slot's next draw)
        self._slot_keys = self._place_batch(
            jnp.zeros((batch_size, 2), jnp.uint32)
        )
        self._nout = zi()
        # admission accounting: (rows x tokens) pushed through prefill
        # cells, and how many cells ran — the O(prompt·pool) replay this
        # machinery replaced would have counted batch_size x prompt per
        # request here
        self.admission_rowsteps = 0
        self.admission_prefills = 0
        # device-to-host reads the engine made (`_to_host`): a token per
        # occupied slot per tick, a first token and a prompt's last
        # token per admission
        self.host_reads = 0
        # request latency tracking (wall): submit time per uid until the
        # first token, then last-token time per slot for inter-token
        # gaps — populated only when telemetry is enabled
        self._t_submit: dict[int, float] = {}
        self._t_last_tok: dict[int, float] = {}
        # uids admitted-or-queued but not yet finished: duplicate-uid
        # submissions are rejected while the first is live (they would
        # clobber its TTFT accounting and collide its `request_key`
        # sampling stream); reuse after finish is legal
        self._inflight: set[int] = set()

    # -- placement / compilation hooks (identity on a single device) --------

    def _place_params(self, params: Any) -> Any:
        return params

    def _place_cache(self, cache: Any) -> Any:
        return cache

    def _place_batch(self, x: jax.Array) -> jax.Array:
        return x

    def _place_tbl(self, x: jax.Array) -> jax.Array:
        return x

    def _init_cache(self) -> Any:
        if self.paging is not None:
            return self.model.init_cache_paged(
                self.batch, self.paging.n_pages, self._page
            )
        return self.model.init_cache(self.batch)

    def _paging_shards(self) -> int:
        """Page-pool shard count: the mesh data-axis size for sharded
        engines (a slot's pages live on the slot's shard), 1 here."""
        return 1

    def _slot_shard(self, slot: int) -> int:
        return slot // (self.batch // self._paging_shards())

    def _tbl_device(self) -> jax.Array:
        return self._place_tbl(jnp.asarray(self._tbl))

    def _compile_decode(self) -> Callable:
        probe = obs.get().probe
        if self._pg is not None:
            model, page = self.model, self._page
            cell = probe.track(
                "serve.decode_step",
                jax.jit(lambda p, c, t, pos, tbl: model.decode_step_paged(
                    p, c, t, pos, tbl, page
                )),
            )

            def step(params, cache, tok, pos):
                return cell(params, cache, tok, pos, self._tbl_device())

            return step
        return probe.track(
            "serve.decode_step", jax.jit(self.model.decode_step)
        )

    def _admission_rows(self, n: int) -> int:
        """Prefill-cell row count for `n` admitted prompts (sharded
        engines pad to the mesh data-axis multiple; extra rows repeat
        the last prompt and their outputs are discarded)."""
        return n

    def _admission_cell(self, rows: int):
        """(prefill, seat, place_prompts) callables for one admission
        batch width. The base engine shares two shape-polymorphic jits;
        `ShardedEngine` compiles per-width cells with explicit mesh
        shardings so the pool cache is seated without leaving its
        placement."""
        if not hasattr(self, "_prefill_jit"):
            probe = obs.get().probe
            self._prefill_jit = probe.track(
                "serve.prefill", jax.jit(self.model.prefill)
            )
            seat_fn = (
                functools.partial(
                    seating.scatter_pages, layouts=self._layouts
                )
                if self._pg is not None
                else seating.scatter_slots
            )
            self._seat_jit = probe.track(
                "serve.seat", jax.jit(seat_fn, donate_argnums=0),
                donate=(0,),
            )
        return self._prefill_jit, self._seat_jit, lambda p: p

    def _chunk_cell(self, c: int, rows: int):
        """(step, init_rows_cache, place_toks) for the chunked-prefill
        cell of chunk width `c`. One compiled cell per width (the last
        chunk pads to `c` and selects its real last-step logits), so
        chunked admission obeys the same zero-recompile-after-warmup
        discipline as the other cells."""
        if not hasattr(self, "_chunk_jit"):
            self._chunk_jit = obs.get().probe.track(
                "serve.chunk", jax.jit(_chunk_prefill_fn(self.model))
            )
        return (
            self._chunk_jit,
            lambda: self.model.init_cache(rows),
            lambda x: jnp.asarray(x, jnp.int32),
        )

    # -- queue / admission --------------------------------------------------

    @driver_thread_only
    def submit(self, req: Request) -> None:
        if req.prompt.shape[0] == 0:
            # reject here: an empty prompt has no prefill logits to
            # derive the first token from (admission would crash deep
            # in the prefill cell with an opaque shape error)
            raise ValueError(f"request {req.uid}: empty prompt")
        if req.max_new <= 0:
            # admission derives the first token from the prefill logits
            # unconditionally, so even max_new=0 would emit one token
            # and violate the declared bound — reject at the boundary
            raise ValueError(
                f"request {req.uid}: max_new must be >= 1, "
                f"got {req.max_new}"
            )
        if req.uid in self._inflight:
            raise ValueError(
                f"request {req.uid}: uid already in flight — a "
                f"duplicate would clobber the live request's TTFT "
                f"accounting and collide its sampling stream; wait for "
                f"it to finish or submit under a fresh uid"
            )
        if not self.admissible(int(req.prompt.shape[0]), req.max_new):
            # never satisfiable: its worst-case page need exceeds the
            # whole usable pool of a shard, so no amount of waiting for
            # other tenants to finish can ever seat it — typed rejection
            # at the boundary instead of an eternal queue stall
            raise PagesExhaustedError(
                f"request {req.uid}: prompt {int(req.prompt.shape[0])} + "
                f"max_new {req.max_new} needs "
                f"{self._worst_pages(int(req.prompt.shape[0]), req.max_new)}"
                f" pages, but the pool has only "
                f"{self._pg.usable_per_shard} usable per shard"
            )
        self._inflight.add(req.uid)
        tel = obs.get()
        if tel.enabled:
            self._t_submit[req.uid] = time.perf_counter()
            # lineage root: mints the request id at the queue boundary
            tel.tracer.instant(
                "serve/submit", cat="serve",
                request_id=f"serve:{req.uid}",
                prompt_len=int(req.prompt.shape[0]),
            )
        self._queue.append(req)

    def _worst_pages(self, prompt_len: int, max_new: int) -> int:
        """Worst-case pages a request can ever hold: prompt + max_new
        tokens write positions 0..prompt_len+max_new-2 (the last
        generated token is never fed back)."""
        return pages_for_position(
            prompt_len + max_new - 2, self._page, self._span
        )

    def admissible(self, prompt_len: int, max_new: int) -> bool:
        """False when the request can NEVER be seated (worst-case page
        need exceeds a shard's whole usable pool). Host-side arithmetic
        only — safe to call from the frontend's event loop."""
        if self._pg is None:
            return True
        return (
            self._worst_pages(prompt_len, max_new)
            <= self._pg.usable_per_shard
        )

    def _pick_seat(self, req: Request, free: list) -> Optional[int]:
        """Claim a free slot (and, paged, reserve the request's
        worst-case pages on that slot's shard). Returns None when no
        shard can cover the reservation right now — admission defers
        until running tenants free pages."""
        if self._pg is None:
            return free.pop(0)
        worst = self._worst_pages(int(req.prompt.shape[0]), req.max_new)
        tried: set[int] = set()
        for i, slot in enumerate(free):
            shard = self._slot_shard(slot)
            if shard in tried:
                continue
            tried.add(shard)
            try:
                self._pg.reserve(req.uid, worst, shard)
            except PagesExhaustedError:
                continue
            return free.pop(i)
        return None

    def _admit(self) -> None:
        # admission rounds: requests finishing at admission (EOS on
        # their first token) never occupy a slot, so their freed seats
        # go back into the next round on the same tick
        if self.chunk_tokens is not None:
            self._start_chunks()
        while self._queue:
            free = [i for i in range(self.batch) if self._slots[i] is None]
            if not free:
                return
            pairs: list = []
            blocked = False
            while self._queue and free:
                req = self._queue[0]
                slot = self._pick_seat(req, free)
                if slot is None:
                    # page pool can't cover this request yet: hold the
                    # FIFO head (and everything behind it) until pages
                    # free up — deferral, not rejection
                    blocked = True
                    break
                self._queue.pop(0)
                pairs.append((slot, req))
            if not pairs:
                return
            groups: dict[int, list] = {}
            for slot, req in pairs:
                groups.setdefault(int(req.prompt.shape[0]), []).append(
                    (slot, req)
                )
            for s_len, grp in groups.items():
                self._admit_group(s_len, grp)
            if blocked:
                return

    def _admit_group(self, s_len: int, pairs: list) -> None:
        """One batched prefill + scatter-seat for same-length prompts."""
        tel = obs.get()
        tagged = (
            {"request_ids": [f"serve:{r.uid}" for _, r in pairs]}
            if tel.enabled
            else {}
        )
        with tel.span(
            "serve/admit", cat="serve", s_len=s_len, n=len(pairs),
            **self._admit_span_attrs(), **tagged,
        ):
            self._admit_group_inner(tel, s_len, pairs, tagged)

    def _admit_span_attrs(self) -> dict:
        """Extra attrs for the admission span (`ShardedEngine` reports
        its mesh/width placement here)."""
        return {}

    def _admit_group_inner(
        self, tel, s_len: int, pairs: list, tagged: Optional[dict] = None,
    ) -> None:
        tagged = {} if tagged is None else tagged
        reqs = [r for _, r in pairs]
        n = len(reqs)
        rows = self._admission_rows(n)
        prompts = jnp.stack(
            [jnp.asarray(r.prompt, jnp.int32) for r in reqs]
        )
        if rows > n:
            prompts = jnp.concatenate(
                [prompts,
                 jnp.broadcast_to(prompts[-1:], (rows - n, s_len))]
            )
        prefill, seat, place = self._admission_cell(rows)
        with tel.span(
            "serve/prefill", cat="serve", s_len=s_len, rows=rows,
            **tagged,
        ):
            logits, cache_rows = prefill(self.params, place(prompts))
            tel.block(logits)
        self.admission_rowsteps += rows * s_len
        self.admission_prefills += 1
        tel.registry.counter("serve.admission_rowsteps").add(rows * s_len)
        tel.registry.counter("serve.admission_prefills").inc()
        # the first generated token comes from the prefill's final
        # logits — the same source `generate` uses, which is what makes
        # the two paths token-identical
        if self.greedy:
            firsts = jnp.argmax(logits[:n], axis=-1).astype(jnp.int32)
        else:
            keys = jnp.stack(
                [request_key(self.key, r.uid) for r in reqs]
            )
            firsts = sample_tokens(
                logits[:n], jax.vmap(jax.random.fold_in)(
                    keys, jnp.zeros((n,), jnp.int32)
                ),
                temperature=self.temperature, top_k=self.top_k,
            )
        src, dst = [], []
        for j, (slot, req) in enumerate(pairs):
            first = int(self._to_host(firsts[j]))
            req.output.append(first)
            if tel.enabled:
                t_now = time.perf_counter()
                t0 = self._t_submit.pop(req.uid, None)
                if t0 is not None:
                    tel.registry.histogram("serve.ttft_s").observe(
                        t_now - t0
                    )
                self._t_last_tok[slot] = t_now
            if (
                req.eos is not None and first == req.eos
            ) or len(req.output) >= req.max_new:
                # EOS-on-first-token guard: the request finishes at
                # admission and must never occupy the slot — seating it
                # would leak the slot for requests finishing on the same
                # tick they were admitted.
                req.done = True
                self._inflight.discard(req.uid)
                if self._pg is not None:
                    self._pg.free(req.uid)  # releases the reservation
                self.active = self.active.at[slot].set(False)
                self._t_last_tok.pop(slot, None)
                if tel.enabled:
                    tel.tracer.instant(
                        "serve/finish", cat="serve",
                        request_id=f"serve:{req.uid}",
                        n_tokens=len(req.output),
                        at_admission=True,
                    )
                continue
            src.append(j)
            dst.append(slot)
            self._slots[slot] = req
            self._seat_slot_state(req, slot, s_len, first)
        if src:
            with tel.span(
                "serve/seat", cat="serve", n=len(src), **tagged,
            ):
                src_a = jnp.asarray(src, jnp.int32)
                dst_a = jnp.asarray(dst, jnp.int32)
                if self._pg is not None:
                    self.cache = seat(
                        self.cache, cache_rows, src_a, dst_a,
                        jnp.asarray(self._tbl[dst], jnp.int32),
                    )
                else:
                    self.cache = seat(self.cache, cache_rows, src_a, dst_a)
                tel.block(self.cache)

    def _seat_slot_state(
        self, req: Request, slot: int, s_len: int, first: int
    ) -> None:
        """Per-slot engine state for a freshly seated request (shared by
        batched admission and chunked-prefill completion). Paged: draw
        the prompt's pages from the request's reservation into the
        indirection table before its cache rows are scattered."""
        if self._pg is not None:
            p0 = pages_for_position(s_len - 1, self._page, self._span)
            for j in range(p0):
                self._tbl[slot, j] = self._pg.alloc(req.uid)
            self._npages[slot] = p0
            self._hpos[slot] = s_len - 1
        self.pos = self.pos.at[slot].set(s_len - 1)
        self.tokens = self.tokens.at[slot].set(first)
        self.active = self.active.at[slot].set(True)
        self._ctok = self._ctok.at[slot].set(
            int(self._to_host(req.prompt[-1])))
        self._cpos = self._cpos.at[slot].set(s_len - 1)
        self._slot_keys = self._slot_keys.at[slot].set(
            request_key(self.key, req.uid)
        )
        self._nout = self._nout.at[slot].set(1)

    # -- chunked prefill ----------------------------------------------------

    def _reserve_chunk(self, req: Request) -> Optional[int]:
        """Reserve worst-case pages for a chunking request; returns the
        shard the reservation (and the eventual seat) lives on, or None
        to retry next tick."""
        if self._pg is None:
            return 0
        worst = self._worst_pages(int(req.prompt.shape[0]), req.max_new)
        shard = max(
            range(self._pg.n_shards), key=self._pg.available
        )
        try:
            self._pg.reserve(req.uid, worst, shard)
        except PagesExhaustedError:
            return None
        return shard

    def _start_chunks(self) -> None:
        """Move long prompts off the admission queue into chunked
        prefill. Short prompts behind a long one admit normally — the
        starvation the chunk interleave exists to prevent. Requests
        whose page reservation can't be covered yet park in
        `_chunk_wait` and retry each tick."""
        c = self.chunk_tokens
        longs = [
            r for r in self._queue if int(r.prompt.shape[0]) > c
        ]
        if longs:
            self._queue = [
                r for r in self._queue if int(r.prompt.shape[0]) <= c
            ]
        tel = obs.get()
        for req in self._chunk_wait + longs:
            shard = self._reserve_chunk(req)
            if shard is None:
                if req not in self._chunk_wait:
                    self._chunk_wait.append(req)
                continue
            if req in self._chunk_wait:
                self._chunk_wait.remove(req)
            rows = self._admission_rows(1)
            _, init_rows, _ = self._chunk_cell(c, rows)
            self._chunks.append(
                _ChunkState(req=req, cache=init_rows(), done=0,
                            shard=shard)
            )
            if tel.enabled:
                tel.tracer.instant(
                    "serve/chunk_start", cat="serve",
                    request_id=f"serve:{req.uid}",
                    prompt_len=int(req.prompt.shape[0]),
                )

    def _chunk_tick(self, tel) -> int:
        """Advance every chunking request by one chunk; seat the ones
        that completed (free pool slot permitting). Returns the number
        of requests still mid-chunk or waiting — they count as engine
        activity so `run()`/frontend drains don't stop early."""
        if self._chunk_wait:
            self._start_chunks()
        for st in list(self._chunks):
            if not st.ready:
                self._chunk_advance(tel, st)
            if st.ready and self._chunk_seat(tel, st):
                self._chunks.remove(st)
        return len(self._chunks) + len(self._chunk_wait)

    def _chunk_advance(self, tel, st: _ChunkState) -> None:
        c = self.chunk_tokens
        rows = self._admission_rows(1)
        step, _, place = self._chunk_cell(c, rows)
        prompt = self._to_host(st.req.prompt).astype(np.int32)
        s = prompt.shape[0]
        lo = st.done
        hi = min(lo + c, s)
        chunk = np.full((c,), prompt[hi - 1], np.int32)
        chunk[: hi - lo] = prompt[lo:hi]
        poss = np.minimum(np.arange(lo, lo + c), hi - 1).astype(np.int32)
        act = jnp.asarray(np.arange(c) < (hi - lo))
        toks = place(np.broadcast_to(chunk, (rows, c)))
        poss2 = place(np.broadcast_to(poss, (rows, c)))
        with tel.span(
            "serve/chunk", cat="serve", lo=lo, hi=hi,
            **({"request_ids": [f"serve:{st.req.uid}"]}
               if tel.enabled else {}),
        ):
            st.logits, st.cache = step(
                self.params, st.cache, toks, poss2, act,
                jnp.asarray(hi - lo - 1, jnp.int32),
            )
            tel.block(st.logits)
        self.admission_rowsteps += rows * (hi - lo)
        tel.registry.counter("serve.admission_rowsteps").add(
            rows * (hi - lo)
        )
        tel.registry.counter("serve.chunk_steps").inc()
        st.done = hi
        if st.done >= s:
            st.ready = True

    def _chunk_seat(self, tel, st: _ChunkState) -> bool:
        """Seat a completed chunked prefill into a free pool slot (on
        the reservation's shard when paged). First token, TTFT, and the
        EOS-on-first-token guard mirror batched admission exactly."""
        req = st.req
        free = [i for i in range(self.batch) if self._slots[i] is None]
        if self._pg is not None:
            free = [i for i in free if self._slot_shard(i) == st.shard]
        if not free:
            return False
        slot = free[0]
        s_len = int(req.prompt.shape[0])
        if self.greedy:
            first = int(self._to_host(jnp.argmax(st.logits[0])))
        else:
            first = int(self._to_host(sample_tokens(
                st.logits[:1],
                jax.vmap(jax.random.fold_in)(
                    request_key(self.key, req.uid)[None],
                    jnp.zeros((1,), jnp.int32),
                ),
                temperature=self.temperature, top_k=self.top_k,
            )[0]))
        req.output.append(first)
        if tel.enabled:
            t_now = time.perf_counter()
            t0 = self._t_submit.pop(req.uid, None)
            if t0 is not None:
                tel.registry.histogram("serve.ttft_s").observe(t_now - t0)
            self._t_last_tok[slot] = t_now
        if (
            req.eos is not None and first == req.eos
        ) or len(req.output) >= req.max_new:
            req.done = True
            self._inflight.discard(req.uid)
            if self._pg is not None:
                self._pg.free(req.uid)
            self._t_last_tok.pop(slot, None)
            if tel.enabled:
                tel.tracer.instant(
                    "serve/finish", cat="serve",
                    request_id=f"serve:{req.uid}",
                    n_tokens=len(req.output),
                    at_admission=True,
                )
            return True
        self._slots[slot] = req
        self._seat_slot_state(req, slot, s_len, first)
        rows = self._admission_rows(1)
        _, seat, _ = self._admission_cell(rows)
        src = jnp.asarray([0], jnp.int32)
        dst = jnp.asarray([slot], jnp.int32)
        with tel.span(
            "serve/seat", cat="serve", n=1, chunked=True,
            **({"request_ids": [f"serve:{req.uid}"]}
               if tel.enabled else {}),
        ):
            if self._pg is not None:
                self.cache = seat(
                    self.cache, st.cache, src, dst,
                    jnp.asarray(self._tbl[[slot]], jnp.int32),
                )
            else:
                self.cache = seat(self.cache, st.cache, src, dst)
            tel.block(self.cache)
        return True

    def _step_single(self, slot: int, token: int, pos: int) -> jax.Array:
        """Compatibility shim (the PR 2/3 replay admission ran prompts
        through this): feed one slot (token, pos) while every other
        slot re-feeds its last-fed state. Retransmitting a slot's
        last-fed (token, pos) is a bitwise no-op for attention caches —
        k/v writes depend only on (token, pos), not on cache contents."""
        self._ctok = self._ctok.at[slot].set(token)
        self._cpos = self._cpos.at[slot].set(pos)
        logits, self.cache = self._decode(
            self.params, self.cache, self._ctok, self._cpos
        )
        return logits

    def _to_host(self, x) -> np.ndarray:
        """`x` as a host array, counting a device-to-host read in
        `host_reads` when it lives on a device."""
        if isinstance(x, jax.Array):
            self.host_reads += 1
        return np.asarray(x)

    @driver_thread_only
    def tick(self) -> int:
        """One decode tick for the whole pool; returns #active slots.
        Its spans tile it: `serve/admission`, `serve/pages`,
        `serve/decode`, `serve/emit`."""
        tel = obs.get()
        with tel.span("serve/tick", cat="serve"):
            return self._tick_inner(tel)

    def _tick_inner(self, tel) -> int:
        with tel.span("serve/admission", cat="serve"):
            self._admit()
            n_chunk = (
                self._chunk_tick(tel) if self.chunk_tokens is not None
                else 0
            )
        if not any(r is not None for r in self._slots):
            return n_chunk
        if self._pg is not None:
            with tel.span("serve/pages", cat="serve"):
                self._map_pages()
        # active slots advance with their pending token; inactive slots
        # re-feed their last-fed state (no junk writes into positions a
        # future tenant's scatter-seat wouldn't overwrite anyway)
        pos = jnp.where(self.active, self.pos + 1, self._cpos)
        toks = jnp.where(self.active, self.tokens, self._ctok)
        tagged = (
            {"request_ids": [
                f"serve:{r.uid}" for r in self._slots if r is not None
            ]}
            if tel.enabled
            else {}
        )
        with tel.span("serve/decode", cat="serve", **tagged):
            logits, self.cache = self._decode(
                self.params, self.cache, toks, pos
            )
            if self.greedy:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                step_keys = jax.vmap(jax.random.fold_in)(
                    self._slot_keys, self._nout
                )
                nxt = sample_tokens(
                    logits, step_keys,
                    temperature=self.temperature, top_k=self.top_k,
                )
            tel.block(nxt)
        # this decode fed (toks, pos) into every slot's cache
        self._ctok = toks
        self._cpos = pos
        self.pos = jnp.where(self.active, pos, self.pos)
        self.tokens = jnp.where(self.active, nxt, self.tokens)
        # every occupied (== active, see test_serve_properties) slot
        # produced one token this tick: one vectorized bump, not a
        # per-slot dispatch on the per-token hot loop
        self._nout = self._nout + self.active.astype(jnp.int32)
        with tel.span("serve/emit", cat="serve"):
            return self._emit(tel, nxt) + n_chunk

    def _map_pages(self) -> None:
        """Page-boundary crossings: every occupied slot writes at
        position _hpos+1 this tick; map any newly needed logical page
        before the decode cell sees the table (the seated reservation
        guarantees alloc succeeds)."""
        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            nw = self._hpos[slot] + 1
            need = pages_for_position(nw, self._page, self._span)
            while self._npages[slot] < need:
                self._tbl[slot, self._npages[slot]] = self._pg.alloc(
                    req.uid
                )
                self._npages[slot] += 1
            self._hpos[slot] = nw

    def _emit(self, tel, nxt: jax.Array) -> int:
        """Each occupied slot's token from the decode (a host read
        each); finished requests free their slot and pages. Returns the
        slots still active."""
        n_active = 0
        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            tok = int(self._to_host(nxt[slot]))
            req.output.append(tok)
            if tel.enabled:
                t_now = time.perf_counter()
                t_prev = self._t_last_tok.get(slot)
                if t_prev is not None:
                    tel.registry.histogram(
                        "serve.inter_token_s"
                    ).observe(t_now - t_prev)
                self._t_last_tok[slot] = t_now
            if (req.eos is not None and tok == req.eos) or len(
                req.output
            ) >= req.max_new:
                req.done = True
                self._inflight.discard(req.uid)
                self._slots[slot] = None
                self.active = self.active.at[slot].set(False)
                self._t_last_tok.pop(slot, None)
                if self._pg is not None:
                    # return the slot's pages and point its table rows
                    # back at scratch: the pool decode re-feeds inactive
                    # slots every tick, and scratch is the only page
                    # those writes are allowed to scribble on
                    self._pg.free(req.uid)
                    self._tbl[slot, :] = self._pg.scratch(
                        self._slot_shard(slot)
                    )
                    self._npages[slot] = 0
                if tel.enabled:
                    tel.tracer.instant(
                        "serve/finish", cat="serve",
                        request_id=f"serve:{req.uid}",
                        n_tokens=len(req.output),
                    )
            else:
                n_active += 1
        return n_active

    def cache_bytes_in_use(self) -> int:
        """Logically resident cache bytes: occupied slots' dense
        per-slot state plus (paged) allocated pages. Drains back to the
        post-construction value (0) when every request finishes — the
        reclamation BENCH_decode asserts. The dense pool's in-use bytes
        count full `max_len` rows per tenant; the paged pool counts only
        mapped pages, which is the whole tenancy win."""
        slot_b, page_b = self._cache_byte_model()
        occupied = sum(r is not None for r in self._slots)
        used = occupied * slot_b
        if self._pg is not None:
            used += self._pg.allocated_pages() * page_b
        return used

    def _cache_byte_model(self) -> tuple:
        """(bytes per occupied slot over dense leaves, bytes per page
        over paged pool leaves), derived from the live cache tree."""
        cached = getattr(self, "_byte_model", None)
        if cached is not None:
            return cached
        flat, _ = jax.tree_util.tree_flatten_with_path(self.cache)
        slot_b = 0
        page_b = 0
        for kp, leaf in flat:
            parts = shd._path_str(kp).split("/")
            nbytes = leaf.size * leaf.dtype.itemsize
            if (
                self._pg is not None
                and seating._leaf_layout(parts, self._layouts) is not None
            ):
                page_b += nbytes // self.paging.n_pages
            else:
                ax = shd.cache_batch_axis(parts)
                slot_b += nbytes // leaf.shape[ax]
        self._byte_model = (slot_b, page_b)
        return self._byte_model

    @driver_thread_only
    def run(self, max_ticks: int = 10_000) -> None:
        for _ in range(max_ticks):
            if self.tick() == 0 and not self._queue:
                break


def generate(
    model: Model,
    params: Any,
    prompts: jax.Array,  # (B, S) int32 — same-length batch
    *,
    max_new: int,
    greedy: bool = True,
    key: Optional[jax.Array] = None,
    temperature: float = 1.0,
    top_k: int = 0,
) -> jax.Array:
    """Simple batched generate: one prefill + max_new decode steps.
    Returns (B, max_new) int32.

    With `greedy=False` and a `key`, row b's token t is drawn with
    `fold_in(fold_in(key, b), t)` — the engine's per-request schedule
    with the row index as uid, so a request submitted to an `Engine`
    built on the same key (uid == row) produces the same stream."""
    b, s = prompts.shape
    _reject_enc_dec(model.cfg, "generate")
    sampling = not greedy and key is not None
    last_logits, cache = jax.jit(model.prefill)(params, prompts)
    decode = jax.jit(model.decode_step)
    if sampling:
        row_keys = jax.vmap(lambda r: request_key(key, r))(jnp.arange(b))
        draw = lambda lg, t: sample_tokens(
            lg, jax.vmap(jax.random.fold_in)(
                row_keys, jnp.full((b,), t, jnp.int32)
            ),
            temperature=temperature, top_k=top_k,
        )
        tok = draw(last_logits, 0)
    else:
        tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    outs = []
    for t in range(max_new):
        outs.append(tok)
        pos = jnp.full((b,), s + t, jnp.int32)
        logits, cache = decode(params, cache, tok, pos)
        if sampling:
            tok = draw(logits, t + 1)
        else:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.stack(outs, axis=1)
