"""Paged KV-cache property harness: paged-vs-dense equivalence,
allocator fuzz, and chunked-prefill starvation regressions.

The paged pool replaces dense per-tenant `max_len` cache rows with
fixed-size pages behind a host-side slot->page indirection table
(`serve.paging` + the paged branches of `models.transformer` /
`serve.seating` / `serve.engine`). None of that machinery is allowed to
change a single emitted token: under hypothesis-driven random
admit/tick/finish interleavings, every request's stream from a paged
engine must be token-for-token identical to the dense-pool engine AND
to the solo prefill+decode reference — for attention and recurrent
architectures, prompts shorter than one page and prompts crossing page
boundaries, on one device (fast lane) and on the 8-device data mesh
(slow-marked, scripts/ci.sh).

The allocator is fuzzed directly: random reserve/alloc/free(shed)
sequences must never double-allocate or leak a page (`check_invariants`
audits the full partition after every op), must raise *typed*
exhaustion errors, and must lay out pages deterministically (identical
op sequences -> identical physical layouts — what makes paged runs
reproducible).

Chunked prefill (`chunk_tokens`) is pinned by a starvation regression:
a max-length prompt co-submitted with shorts must not delay the shorts'
first tokens at all — they admit on the first tick while the long
prompt's prefill proceeds in chunks — and the chunked path must be
bitwise identical between the dense and paged pools.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import configs
from repro.models import api
from repro.serve import engine as E
from repro.serve import seating
from repro.serve.paging import (
    PageAllocator,
    PagesExhaustedError,
    PagingConfig,
    pages_for_position,
    validate_page_size,
)
from slot_layouts import PINNED, drive_pinned, pinned_requests

ARCHS = ("qwen3_8b", "recurrentgemma_2b", "rwkv6_3b")

MAX_SEQ = 24
PAGE = 4  # divides qwen3's max_seq cap AND recurrentgemma's window (8)
N_PAGES = 16
# sub-page prompts (2, 3), one exact page (4), page-crossing (5, 9)
PROMPT_LENS = (2, 3, 5, 9)
PAGING = PagingConfig(page_size=PAGE, n_pages=N_PAGES)


@pytest.fixture(scope="module")
def built():
    out = {}
    for name in ARCHS:
        cfg = configs.reduced(name)
        model = api.build_model(cfg, tp=1, max_seq=MAX_SEQ)
        params = model.init(jax.random.PRNGKey(0))
        span = validate_page_size(PAGE, model.attn_capacities())
        # shared jitted cells so hypothesis examples don't retrace
        prefill = jax.jit(model.prefill)
        decode = jax.jit(model.decode_step)
        seat_dense = jax.jit(seating.scatter_slots, donate_argnums=0)
        chunk = jax.jit(E._chunk_prefill_fn(model))
        if span:
            decode_paged = jax.jit(
                lambda p, c, t, pos, tbl, _m=model:
                _m.decode_step_paged(p, c, t, pos, tbl, PAGE)
            )
            seat_paged = jax.jit(
                functools.partial(
                    seating.scatter_pages,
                    layouts=model.page_layouts(PAGE),
                ),
                donate_argnums=0,
            )
        else:  # pure recurrent: paging degenerates to the dense pool
            decode_paged, seat_paged = None, None

        class FastEngine(E.Engine):
            def _compile_decode(self, _dense=decode, _paged=decode_paged):
                if self._pg is None:
                    return _dense

                def step(params, cache, tok, pos):
                    return _paged(
                        params, cache, tok, pos, self._tbl_device()
                    )

                return step

            def _admission_cell(
                self, rows, _p=prefill, _sd=seat_dense, _sp=seat_paged
            ):
                seat = _sp if self._pg is not None else _sd
                return _p, seat, lambda p: p

            def _chunk_cell(self, c, rows, _chunk=chunk, _m=model):
                return (
                    _chunk,
                    lambda: _m.init_cache(rows),
                    lambda x: jnp.asarray(x, jnp.int32),
                )

        out[name] = (model, params, FastEngine, prefill, decode)
    return out


def _ref_stream(prefill, decode, params, req: E.Request) -> list:
    """Solo greedy prefill+decode reference (the `generate` recipe),
    truncated the way the engine truncates."""
    prompt = jnp.asarray(req.prompt, jnp.int32)[None]
    s = prompt.shape[1]
    logits, cache = prefill(params, prompt)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = []
    for t in range(req.max_new):
        out.append(int(tok[0]))
        if req.eos is not None and out[-1] == req.eos:
            break
        if len(out) >= req.max_new:
            break
        pos = jnp.full((1,), s + t, jnp.int32)
        logits, cache = decode(params, cache, tok, pos)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return out


def _make_requests(cfg, rng, n, *, eos_pool=None):
    reqs = []
    for i in range(n):
        s_len = int(rng.choice(PROMPT_LENS))
        prompt = jax.random.randint(
            jax.random.PRNGKey(1000 + i), (s_len,), 0, cfg.vocab
        )
        eos = None
        if eos_pool is not None and rng.random() < 0.4:
            eos = int(rng.choice(eos_pool))
        reqs.append(
            E.Request(
                uid=i, prompt=prompt,
                max_new=int(rng.integers(1, 5)), eos=eos,
            )
        )
    return reqs


def _drive_random_interleaving(eng, reqs, rng, max_steps=200):
    pending = list(reqs)
    steps = 0
    while (pending or eng._queue or eng._chunks or eng._chunk_wait
           or any(s is not None for s in eng._slots)) and steps < max_steps:
        steps += 1
        if pending and (rng.random() < 0.6 or not eng._queue):
            for _ in range(int(rng.integers(1, 3))):
                if pending:
                    eng.submit(pending.pop(0))
        eng.tick()
    assert steps < max_steps, "interleaving did not drain"


# ---------------------------------------------------------------------------
# Paged vs dense vs reference equivalence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,case", [
    *[pytest.param(n, None, id=n) for n in ARCHS],
    *[pytest.param("qwen3_8b", c, id=f"qwen3_8b-{c}") for c in PINNED],
])
@settings(max_examples=4, deadline=None)
@given(
    batch_size=st.sampled_from([2, 3]),
    n_reqs=st.integers(1, 5),
    seed=st.integers(0, 10_000),
)
def test_paged_engine_matches_dense_and_generate(
    built, name, case, batch_size, n_reqs, seed
):
    """Token-for-token: paged engine == dense engine == solo reference,
    for every request, under the same random admit/tick interleaving —
    with prompts both shorter than a page and crossing page
    boundaries, EOS cuts, and slot/page recycling — and under the
    pinned slot layouts (`slot_layouts.PINNED`)."""
    model, params, FastEngine, prefill, decode = built[name]
    if case is not None:
        streams = {}
        for label, kw in (
            ("dense", {}), ("paged", {"paging": PAGING}),
        ):
            reqs, late = pinned_requests(
                model.cfg, np.random.default_rng(seed), case,
                lambda r: _ref_stream(prefill, decode, params, r),
            )
            eng = FastEngine(model, params, batch_size=len(reqs), **kw)
            reqs = drive_pinned(eng, reqs, late, case)
            if eng._pg is not None:
                eng._pg.check_invariants()
                assert eng._pg.allocated_pages() == 0, "pages leaked"
            streams[label] = {r.uid: r.output for r in reqs}
        assert streams["paged"] == streams["dense"]
        for r in reqs:
            assert r.done, r.uid
            ref = _ref_stream(prefill, decode, params, r)
            assert r.output == ref, (case, r.uid, r.output, ref)
        return
    probe = _ref_stream(
        prefill, decode, params,
        E.Request(uid=0, prompt=jax.random.randint(
            jax.random.PRNGKey(1000), (PROMPT_LENS[0],), 0,
            model.cfg.vocab
        ), max_new=4),
    )
    streams = {}
    for label, kw in (
        ("dense", {}), ("paged", {"paging": PAGING}),
    ):
        rng = np.random.default_rng(seed)
        reqs = _make_requests(model.cfg, rng, n_reqs, eos_pool=probe)
        eng = FastEngine(model, params, batch_size=batch_size, **kw)
        _drive_random_interleaving(eng, reqs, rng)
        if eng._pg is not None:
            eng._pg.check_invariants()
            assert eng._pg.allocated_pages() == 0, "pages leaked"
        streams[label] = {r.uid: r.output for r in reqs}
        for r in reqs:
            assert r.done, (label, r.uid)
    assert streams["paged"] == streams["dense"]
    rng = np.random.default_rng(seed)
    for r in _make_requests(model.cfg, rng, n_reqs, eos_pool=probe):
        ref = _ref_stream(prefill, decode, params, r)
        assert streams["paged"][r.uid] == ref, (name, r.uid)


@pytest.mark.parametrize("name", ("qwen3_8b", "recurrentgemma_2b"))
def test_page_boundary_prompt_lengths(built, name):
    """Deterministic pin of the layout edge cases: prompts of one
    sub-page, exactly one page, and page-crossing lengths all decode
    to the reference stream through the paged pool."""
    model, params, FastEngine, prefill, decode = built[name]
    eng = FastEngine(model, params, batch_size=2, paging=PAGING)
    reqs = [
        E.Request(uid=i, prompt=jax.random.randint(
            jax.random.PRNGKey(40 + i), (s_len,), 0, model.cfg.vocab
        ), max_new=5)
        for i, s_len in enumerate((2, PAGE, PAGE + 1, 2 * PAGE + 1))
    ]
    for r in reqs:
        eng.submit(r)
    eng.run(max_ticks=60)
    for r in reqs:
        assert r.done
        ref = _ref_stream(prefill, decode, params, r)
        assert r.output == ref, (name, r.uid, r.output, ref)


def test_paged_cache_bytes_reclaimed(built):
    """`cache_bytes_in_use` accounting: zero at rest, grows while
    tenants hold pages, and returns exactly to the initial value once
    the pool drains (no leaked pages, no phantom residency)."""
    model, params, FastEngine, _, _ = built["qwen3_8b"]
    eng = FastEngine(model, params, batch_size=2, paging=PAGING)
    initial = eng.cache_bytes_in_use()
    assert initial == 0
    reqs = [
        E.Request(uid=i, prompt=jax.random.randint(
            jax.random.PRNGKey(60 + i), (5,), 0, model.cfg.vocab
        ), max_new=4)
        for i in range(3)
    ]
    for r in reqs:
        eng.submit(r)
    peak = 0
    for _ in range(40):
        n = eng.tick()
        peak = max(peak, eng.cache_bytes_in_use())
        if n == 0 and not eng._queue:
            break
    assert all(r.done for r in reqs)
    assert peak > initial
    assert eng.cache_bytes_in_use() == initial


def test_pure_recurrent_paging_degenerates_to_dense(built):
    """rwkv6 has nothing to page (span == 0): a paged engine builds the
    ordinary dense cache, runs no allocator, and still streams the
    reference tokens."""
    model, params, FastEngine, prefill, decode = built["rwkv6_3b"]
    eng = FastEngine(model, params, batch_size=2, paging=PAGING)
    assert eng._pg is None
    assert jax.tree.structure(
        eng.cache
    ) == jax.tree.structure(model.init_cache(2))
    req = E.Request(uid=0, prompt=jax.random.randint(
        jax.random.PRNGKey(70), (5,), 0, model.cfg.vocab
    ), max_new=4)
    eng.submit(req)
    eng.run(max_ticks=20)
    assert req.output == _ref_stream(prefill, decode, params, req)


# ---------------------------------------------------------------------------
# Allocator fuzz + invariants
# ---------------------------------------------------------------------------


def _random_alloc_ops(rng, n_ops):
    """A random op tape: (op, owner) pairs with owner ids drawn small
    so reserve/alloc/free collide and interleave."""
    ops = []
    for _ in range(n_ops):
        ops.append((
            rng.choice(["reserve", "alloc", "free", "shed"]),
            int(rng.integers(0, 6)),
            int(rng.integers(1, 5)),  # reserve size
        ))
    return ops


def _replay(alloc, ops):
    """Run an op tape, auditing invariants after every op; returns the
    layout trace (what each alloc handed out) for determinism checks."""
    trace = []
    for op, owner, n in ops:
        shard = owner % alloc.n_shards
        try:
            if op == "reserve":
                alloc.reserve(owner, n, shard)
                trace.append(("reserve", owner, n))
            elif op == "alloc":
                trace.append(("alloc", owner, alloc.alloc(owner)))
            else:  # free / shed are both a full release
                trace.append(("free", owner, alloc.free(owner)))
        except PagesExhaustedError:
            trace.append(("exhausted", owner, None))
        except ValueError:
            trace.append(("invalid", owner, None))
        alloc.check_invariants()
    return trace


@settings(max_examples=15, deadline=None)
@given(
    n_pages=st.sampled_from([8, 12, 16]),
    n_shards=st.sampled_from([1, 2]),
    seed=st.integers(0, 10_000),
)
def test_allocator_fuzz_invariants_and_determinism(
    n_pages, n_shards, seed
):
    """Random reserve/alloc/free/shed tapes: the page partition stays
    exact after every op (no double-alloc, no leak, reservations
    consistent), errors are typed, and an identical tape on a fresh
    allocator replays the identical physical layout."""
    if n_pages % n_shards:
        n_pages += n_shards - (n_pages % n_shards)
    rng = np.random.default_rng(seed)
    ops = _random_alloc_ops(rng, 60)
    a = _replay(PageAllocator(n_pages, n_shards), ops)
    b = _replay(PageAllocator(n_pages, n_shards), ops)
    assert a == b, "allocator layout is not deterministic"
    # full release drains everything
    alloc = PageAllocator(n_pages, n_shards)
    _replay(alloc, ops)
    for owner in range(6):
        alloc.free(owner)
    alloc.check_invariants()
    assert alloc.allocated_pages() == 0
    for s in range(n_shards):
        assert alloc.available(s) == alloc.usable_per_shard


def test_allocator_typed_errors():
    """The failure surface is typed, not corrupted state: exhaustion is
    PagesExhaustedError, misuse (double reserve, alloc without
    reservation) is ValueError, and scratch is never handed out."""
    alloc = PageAllocator(8, 2)  # 4 pages/shard: 3 usable + scratch
    with pytest.raises(PagesExhaustedError):
        alloc.reserve("big", 4, 0)  # > 3 usable
    alloc.reserve("a", 3, 0)
    with pytest.raises(ValueError):
        alloc.reserve("a", 1, 0)  # double reserve
    with pytest.raises(PagesExhaustedError):
        alloc.reserve("b", 1, 0)  # shard 0 fully reserved
    alloc.reserve("b", 1, 1)  # other shard unaffected
    with pytest.raises(ValueError):
        alloc.alloc("nobody")
    pages = [alloc.alloc("a") for _ in range(3)]
    assert alloc.scratch(0) not in pages
    assert alloc.scratch(1) not in pages
    with pytest.raises(PagesExhaustedError):
        alloc.alloc("a")  # reservation exhausted, no slack
    assert alloc.free("a") == 3
    alloc.check_invariants()


def test_submit_rejects_never_satisfiable_request(built):
    """A request whose worst-case page need exceeds a whole shard's
    usable pool can never seat: `submit` raises the typed
    PagesExhaustedError at the boundary instead of stalling the queue
    forever."""
    model, params, FastEngine, _, _ = built["qwen3_8b"]
    tiny = PagingConfig(page_size=PAGE, n_pages=3)  # 2 usable pages
    eng = FastEngine(model, params, batch_size=2, paging=tiny)
    with pytest.raises(PagesExhaustedError):
        eng.submit(E.Request(
            uid=0,
            prompt=jax.random.randint(
                jax.random.PRNGKey(0), (9,), 0, model.cfg.vocab
            ),
            max_new=8,  # worst case 4 pages > 2 usable
        ))
    assert 0 not in eng._inflight  # rejection left no residue
    assert eng.admissible(2, 2)
    assert not eng.admissible(9, 8)


def test_admission_defers_until_pages_free(built):
    """Exhaustion at admission is deferral, not rejection: two
    satisfiable-but-not-together requests serialize through the page
    pool and both finish with reference streams."""
    model, params, FastEngine, prefill, decode = built["qwen3_8b"]
    # 5 usable pages; each request's worst case is 4 -> one at a time
    eng = FastEngine(
        model, params, batch_size=2,
        paging=PagingConfig(page_size=PAGE, n_pages=6),
    )
    reqs = [
        E.Request(uid=i, prompt=jax.random.randint(
            jax.random.PRNGKey(80 + i), (9,), 0, model.cfg.vocab
        ), max_new=6)
        for i in range(2)
    ]
    for r in reqs:
        eng.submit(r)
    eng.tick()
    # only one seated; the other is held in FIFO order, still queued
    assert sum(s is not None for s in eng._slots) == 1
    assert len(eng._queue) == 1
    eng.run(max_ticks=40)
    for r in reqs:
        assert r.done
        assert r.output == _ref_stream(prefill, decode, params, r), r.uid
    eng._pg.check_invariants()
    assert eng._pg.allocated_pages() == 0


# ---------------------------------------------------------------------------
# Chunked prefill
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ("qwen3_8b", "recurrentgemma_2b"))
def test_chunked_prefill_does_not_starve_shorts(built, name):
    """Starvation regression: a max-length prompt submitted FIRST must
    not delay co-submitted shorts — the shorts' first tokens appear on
    the very first tick (batched admission) and are the first TTFT
    observations the telemetry layer sees, while the long prompt
    prefills in chunks and lands within its bounded tick budget."""
    from repro import obs

    model, params, FastEngine, prefill, decode = built[name]
    chunk = PAGE
    long_len = MAX_SEQ - 6  # max-length prompt for this pool
    saved = obs.get()
    tel = obs.configure(enabled=True)
    try:
        eng = FastEngine(
            model, params, batch_size=3, paging=PAGING,
            chunk_tokens=chunk,
        )
        long = E.Request(uid=0, prompt=jax.random.randint(
            jax.random.PRNGKey(90), (long_len,), 0, model.cfg.vocab
        ), max_new=4)
        shorts = [
            E.Request(uid=1 + i, prompt=jax.random.randint(
                jax.random.PRNGKey(91 + i), (3,), 0, model.cfg.vocab
            ), max_new=4)
            for i in range(2)
        ]
        ttft = tel.registry.histogram("serve.ttft_s")
        eng.submit(long)  # ahead of the shorts in FIFO order
        for r in shorts:
            eng.submit(r)
        eng.tick()
        for r in shorts:
            assert len(r.output) >= 1, "short starved behind long prefill"
        # the TTFT histogram saw exactly the two shorts — the long's
        # first token is still chunks away
        assert ttft.count == 2, ttft.count
        # the long prompt's first token needs ceil(long_len/chunk)
        # chunk ticks; allow one extra for seating
        budget = -(-long_len // chunk) + 1
        ticks = 1
        while not long.output and ticks < budget + 1:
            eng.tick()
            ticks += 1
        assert long.output, f"long prompt got no token in {ticks} ticks"
        assert ticks <= budget, (ticks, budget)
        assert ttft.count == 3, ttft.count
        eng.run(max_ticks=40)
        assert long.done and all(r.done for r in shorts)
    finally:
        obs.install(saved)
    for r in shorts:  # chunking must not perturb the shorts' streams
        assert r.output == _ref_stream(prefill, decode, params, r), r.uid


@pytest.mark.parametrize("name", ("qwen3_8b", "recurrentgemma_2b"))
def test_chunked_prefill_paged_matches_dense(built, name):
    """The chunked prefill cell is the same computation over both
    pools: dense-chunked and paged-chunked engines are bitwise
    token-identical on a mixed short/long workload."""
    model, params, FastEngine, _, _ = built[name]
    def mkreqs():
        return [
            E.Request(uid=i, prompt=jax.random.randint(
                jax.random.PRNGKey(95 + i), (s_len,), 0, model.cfg.vocab
            ), max_new=4)
            for i, s_len in enumerate((13, 2, 9, 3))
        ]
    outs = {}
    for label, kw in (
        ("dense", {}), ("paged", {"paging": PAGING}),
    ):
        reqs = mkreqs()
        eng = FastEngine(
            model, params, batch_size=2, chunk_tokens=PAGE, **kw
        )
        for r in reqs:
            eng.submit(r)
        eng.run(max_ticks=60)
        assert all(r.done for r in reqs)
        outs[label] = [r.output for r in reqs]
    assert outs["dense"] == outs["paged"]


# ---------------------------------------------------------------------------
# Seating inverses
# ---------------------------------------------------------------------------


def test_scatter_then_gather_pages_roundtrip(built):
    """`gather_pages` inverts `scatter_pages`: seat dense rows into the
    paged pool under a page mapping, gather them back, and recover the
    rows bitwise (paged K/V leaves and dense slot_pos/recurrent leaves
    alike)."""
    model, params, _, prefill, _ = built["qwen3_8b"]
    layouts = model.page_layouts(PAGE)
    span = validate_page_size(PAGE, model.attn_capacities())
    pool = model.init_cache_paged(4, N_PAGES, PAGE)
    prompts = jax.random.randint(
        jax.random.PRNGKey(5), (2, MAX_SEQ), 0, model.cfg.vocab
    )
    _, rows = prefill(params, prompts)
    # two slots, fully mapped, disjoint pages (scratch untouched)
    phys = jnp.asarray(
        [list(range(span)), list(range(span, 2 * span))], jnp.int32
    )
    src = jnp.asarray([0, 1], jnp.int32)
    dst = jnp.asarray([1, 3], jnp.int32)
    pool2 = seating.scatter_pages(
        pool, rows, src, dst, phys, layouts=layouts
    )
    back = seating.gather_pages(pool2, dst, phys, layouts=layouts)
    for a, b in zip(jax.tree.leaves(rows), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_paging_config_and_page_math():
    """PagingConfig validation and the pages_for_position ring cap."""
    with pytest.raises(ValueError):
        PagingConfig(page_size=0, n_pages=8)
    with pytest.raises(ValueError):
        PagingConfig(page_size=4, n_pages=1)
    with pytest.raises(ValueError):
        validate_page_size(5, (24, 8))  # 5 divides neither
    assert validate_page_size(4, (24, 8)) == 6
    assert validate_page_size(4, ()) == 0  # pure recurrent
    assert pages_for_position(-1, 4, 6) == 0
    assert pages_for_position(0, 4, 6) == 1
    assert pages_for_position(3, 4, 6) == 1
    assert pages_for_position(4, 4, 6) == 2
    assert pages_for_position(23, 4, 6) == 6
    # ring wrap: windowed caches cap at span regardless of position
    assert pages_for_position(1000, 4, 6) == 6
    assert pages_for_position(1000, 4, 0) == 0


# ---------------------------------------------------------------------------
# 8-device mesh (slow lane: scripts/ci.sh forces 8 host devices)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices (scripts/ci.sh forces 8 host devices)",
)
@pytest.mark.parametrize("name", ("qwen3_8b", "recurrentgemma_2b"))
@pytest.mark.parametrize("chunk_tokens", (None, PAGE))
def test_sharded_paged_matches_sharded_dense(built, name, chunk_tokens):
    """On the 8-device data mesh, the paged pool (pages sharded over
    the same data axis as the slots they serve) is token-for-token
    identical to the dense sharded pool — with and without chunked
    prefill — and every slot's pages stay on the slot's shard."""
    from repro.launch.mesh import make_smoke_mesh
    from repro.serve import sharded as SH

    model, params, _, _, _ = built[name]
    mesh = make_smoke_mesh(8, 1)
    paging = PagingConfig(page_size=PAGE, n_pages=8 * N_PAGES)

    def mkreqs():
        rng = np.random.default_rng(3)
        return [
            E.Request(uid=i, prompt=jax.random.randint(
                jax.random.PRNGKey(1000 + i),
                (int(rng.choice(PROMPT_LENS)),), 0, model.cfg.vocab
            ), max_new=int(rng.integers(2, 5)))
            for i in range(12)
        ]

    outs = {}
    for label, kw in (
        ("dense", {}),
        ("paged", {"paging": paging, "chunk_tokens": chunk_tokens}),
    ):
        reqs = mkreqs()
        eng = SH.ShardedEngine(
            model, params, batch_size=8, mesh=mesh, **kw
        )
        for r in reqs:
            eng.submit(r)
        mid_checked = False
        for _ in range(60):
            n = eng.tick()
            if eng._pg is not None and any(
                s is not None for s in eng._slots
            ):
                # live audit: every mapped page (non-scratch entries)
                # lives in its slot's shard range
                per = eng._pg.per_shard
                for slot in range(eng.batch):
                    shard = eng._slot_shard(slot)
                    for p in eng._tbl[slot][: eng._npages[slot]]:
                        assert per * shard <= p < per * (shard + 1)
                mid_checked = True
            if n == 0 and not eng._queue:
                break
        assert all(r.done for r in reqs)
        if eng._pg is not None:
            assert mid_checked
            eng._pg.check_invariants()
            assert eng._pg.allocated_pages() == 0
        outs[label] = [r.output for r in reqs]
    assert outs["dense"] == outs["paged"]
