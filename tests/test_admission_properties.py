"""Cross-admission equivalence property harness.

The engine admits by batched prefill + per-slot cache scatter
(`serve.seating`); `generate` / `sharded_generate` run one prefill +
straight decode steps. These are different code paths over the same
math, so the contract is checkable: under hypothesis-driven random
admit/tick/finish interleavings (variable prompt lengths, co-admission,
EOS cuts, slot recycling), every request's token stream from the engine
must be token-for-token identical to its solo `generate` stream — for
attention *and* recurrent (rg-lru, rwkv) architectures, whose caches
scatter seating made first-class engine tenants.

Single-device properties run in the fast lane; the 8-device data/TP
mesh properties are `slow`-marked and run in CI (`scripts/ci.sh`, 8
forced host devices). The file also pins the satellites that ride on
the same machinery: sampling determinism (per-request folded keys:
reproducible across runs and seat order; greedy untouched), the
`sample_tokens` top-k edge cases, seating scatter/gather inverses, and
the typed enc-dec guard with its actionable message.
"""

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
from repro.serve import sharded as SH
from slot_layouts import PINNED, drive_pinned, pinned_requests

# one attention family + both recurrent families: the archs whose
# engine admission the scatter-seat refactor changed most
ARCHS = ("qwen3_8b", "recurrentgemma_2b", "rwkv6_3b")

MAX_SEQ = 24
PROMPT_LENS = (2, 3, 4)  # bounded so prefill cells compile a few shapes


@pytest.fixture(scope="module")
def built():
    out = {}
    for name in ARCHS:
        cfg = configs.reduced(name)
        model = api.build_model(cfg, tp=1, max_seq=MAX_SEQ)
        params = model.init(jax.random.PRNGKey(0))
        # shared jitted cells so hypothesis examples don't retrace
        prefill = jax.jit(model.prefill)
        decode = jax.jit(model.decode_step)

        class FastEngine(E.Engine):
            def _compile_decode(self, _decode=decode):
                return _decode

            def _admission_cell(self, rows, _prefill=prefill):
                if not hasattr(self, "_seat_jit"):
                    self._seat_jit = jax.jit(
                        seating.scatter_slots, donate_argnums=0
                    )
                return _prefill, self._seat_jit, lambda p: p

        out[name] = (model, params, FastEngine, prefill, decode)
    return out


def _ref_stream(prefill, decode, params, req: E.Request) -> list:
    """Solo greedy prefill+decode reference for one request — the
    `generate` recipe on shared jitted cells, truncated the way the
    engine truncates (EOS inclusive, max_new cap)."""
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
    while (pending or eng._queue
           or any(s is not None for s in eng._slots)) and steps < max_steps:
        steps += 1
        if pending and (rng.random() < 0.6 or not eng._queue):
            for _ in range(int(rng.integers(1, 3))):
                if pending:
                    eng.submit(pending.pop(0))
        eng.tick()
    assert steps < max_steps, "interleaving did not drain"


INTERLEAVING_CASES = [
    *[pytest.param(n, None, id=n) for n in ARCHS],
    *[pytest.param("qwen3_8b", c, id=f"qwen3_8b-{c}") for c in PINNED],
]


@pytest.mark.parametrize("name,case", INTERLEAVING_CASES)
@settings(max_examples=5, deadline=None)
@given(
    batch_size=st.sampled_from([2, 3]),
    n_reqs=st.integers(1, 5),
    seed=st.integers(0, 10_000),
)
def test_engine_matches_generate_under_random_interleavings(
    built, name, case, batch_size, n_reqs, seed
):
    """The scatter-seated engine is token-for-token identical to the
    prefill+decode generate path, for every request, under random
    admit/tick interleavings — including recurrent-cache models at
    batch_size > 1 (the lifted PR 3 guard) — and under the pinned slot
    layouts (`PINNED`, which fix their own batch and requests)."""
    model, params, FastEngine, prefill, decode = built[name]
    rng = np.random.default_rng(seed)
    if case is not None:
        reqs, late = pinned_requests(
            model.cfg, rng, case,
            lambda r: _ref_stream(prefill, decode, params, r),
        )
        eng = FastEngine(model, params, batch_size=len(reqs))
        for r in drive_pinned(eng, reqs, late, case):
            assert r.done, r.uid
            ref = _ref_stream(prefill, decode, params, r)
            assert r.output == ref, (case, r.uid, r.output, ref)
        return
    # EOS drawn from the first request's own reference stream, so EOS
    # cuts (including EOS-on-first-token) actually trigger sometimes
    probe = _ref_stream(
        prefill, decode, params,
        E.Request(uid=0, prompt=jax.random.randint(
            jax.random.PRNGKey(1000), (PROMPT_LENS[0],), 0,
            model.cfg.vocab
        ), max_new=4),
    )
    reqs = _make_requests(model.cfg, rng, n_reqs, eos_pool=probe)
    eng = FastEngine(model, params, batch_size=batch_size)
    _drive_random_interleaving(eng, reqs, rng)
    for r in reqs:
        assert r.done, r.uid
        ref = _ref_stream(prefill, decode, params, r)
        assert r.output == ref, (name, r.uid, r.output, ref)


def test_fast_reference_equals_public_generate(built):
    """The shared-jit reference the harness uses IS `generate`: pin the
    two bitwise on one batch so the property above transitively checks
    the public path."""
    model, params, _, prefill, decode = built["qwen3_8b"]
    prompts = jax.random.randint(
        jax.random.PRNGKey(3), (2, 4), 0, model.cfg.vocab
    )
    got = np.asarray(E.generate(model, params, prompts, max_new=5))
    for b in range(2):
        ref = _ref_stream(
            prefill, decode, params,
            E.Request(uid=b, prompt=prompts[b], max_new=5),
        )
        assert got[b].tolist() == ref


@pytest.mark.parametrize("name", ("recurrentgemma_2b", "rwkv6_3b"))
def test_recurrent_batched_engine_decodes_correctly(built, name):
    """Acceptance: recurrent-cache models decode through `Engine` at
    batch_size > 1, token-for-token identical to `generate` — the
    co-admitted pool never corrupts a seated recurrent state."""
    model, params, FastEngine, prefill, decode = built[name]
    # these archs really carry step-advancing caches (the case the
    # lifted PR 3 guard existed for)
    assert api.is_recurrent(model.cfg)
    eng = FastEngine(model, params, batch_size=2)
    reqs = [
        E.Request(
            uid=i,
            prompt=jax.random.randint(
                jax.random.PRNGKey(1000 + i), (4,), 0, model.cfg.vocab
            ),
            max_new=5,
        )
        for i in range(3)  # forces recycling through the 2-slot pool
    ]
    for r in reqs:
        eng.submit(r)
    eng.run(max_ticks=40)
    for r in reqs:
        assert r.done
        assert r.output == _ref_stream(prefill, decode, params, r), r.uid


# ---------------------------------------------------------------------------
# Sampling determinism
# ---------------------------------------------------------------------------


def test_greedy_outputs_unaffected_by_sampling_machinery(built):
    """The greedy path stays pure argmax: an engine built with sampling
    parameters but greedy=True produces the same stream as the default
    engine and as `generate`."""
    model, params, FastEngine, prefill, decode = built["qwen3_8b"]
    outs = []
    for key in (None, jax.random.PRNGKey(99)):
        eng = FastEngine(
            model, params, batch_size=2, greedy=True,
            temperature=0.7, top_k=3, key=key,
        )
        reqs = [
            E.Request(uid=i, prompt=jax.random.randint(
                jax.random.PRNGKey(1000 + i), (3,), 0, model.cfg.vocab
            ), max_new=4)
            for i in range(2)
        ]
        for r in reqs:
            eng.submit(r)
        eng.run(max_ticks=20)
        outs.append([r.output for r in reqs])
    assert outs[0] == outs[1]
    for r_out, req_uid in zip(outs[0], range(2)):
        ref = _ref_stream(
            prefill, decode, params,
            E.Request(uid=req_uid, prompt=jax.random.randint(
                jax.random.PRNGKey(1000 + req_uid), (3,),
                0, model.cfg.vocab
            ), max_new=4),
        )
        assert r_out == ref


def _sampled_outputs(built_entry, model, params, order, *, key):
    FastEngine = built_entry[2]
    eng = FastEngine(
        model, params, batch_size=2, greedy=False,
        temperature=0.8, top_k=5, key=key,
    )
    reqs = {
        uid: E.Request(uid=uid, prompt=jax.random.randint(
            jax.random.PRNGKey(1000 + uid), (3,), 0, model.cfg.vocab
        ), max_new=4)
        for uid in order
    }
    for uid in order:
        eng.submit(reqs[uid])
    eng.run(max_ticks=30)
    return {uid: r.output for uid, r in reqs.items()}


def test_sampling_reproducible_across_runs_and_seat_order(built):
    """Temperature/top-k streams are a function of (key, uid, t) only:
    identical across runs, and invariant to submission order — which
    reshuffles seats, co-tenants and recycling."""
    entry = built["qwen3_8b"]
    model, params = entry[0], entry[1]
    key = jax.random.PRNGKey(7)
    a = _sampled_outputs(entry, model, params, [0, 1, 2], key=key)
    b = _sampled_outputs(entry, model, params, [0, 1, 2], key=key)
    c = _sampled_outputs(entry, model, params, [2, 0, 1], key=key)
    assert a == b, "sampling not reproducible across runs"
    assert a == c, "sampling depends on seat order"
    # a different engine key gives different streams (the key matters)
    d = _sampled_outputs(
        entry, model, params, [0, 1, 2], key=jax.random.PRNGKey(8)
    )
    assert a != d


@pytest.mark.parametrize("case", ["co_admitted", *PINNED])
def test_engine_sampling_matches_generate_schedule(built, case):
    """With uid == row index, the engine's per-request folded keys
    reproduce `generate`'s sampled streams token-for-token: for one
    co-admitted batch, and under the pinned slot layouts (`PINNED`),
    where each request's stream is its `generate` row cut at its EOS or
    `max_new`."""
    model, params, FastEngine, _, _ = built["qwen3_8b"]
    key = jax.random.PRNGKey(21)
    # `max_new` of the co-admitted rows, then of the late arrival or None
    *max_new, late_new = PINNED[case][0] if case in PINNED else [4, 4, None]
    news = max_new + ([late_new] if late_new is not None else [])
    prompts = jax.random.randint(
        jax.random.PRNGKey(5), (len(news), 3), 0, model.cfg.vocab
    )
    ref = np.asarray(E.generate(
        model, params, prompts, max_new=max(news), greedy=False,
        key=key, temperature=0.8, top_k=5,
    )).tolist()
    eng = FastEngine(
        model, params, batch_size=len(max_new), greedy=False,
        temperature=0.8, top_k=5, key=key,
    )
    reqs = [
        E.Request(uid=i, prompt=prompts[i], max_new=m)
        for i, m in enumerate(news)
    ]
    if case == "eos_between":
        cut = next(k for k in range(1, len(ref[1]))
                   if ref[1][k] not in ref[1][:k])
        reqs[1].eos = ref[1][cut]
    if case == "co_admitted":
        for r in reqs:
            eng.submit(r)
        eng.run(max_ticks=20)
    else:
        late = reqs.pop() if late_new is not None else None
        reqs = drive_pinned(eng, reqs, late, case)
    for i, r in enumerate(reqs):
        want = ref[i][:r.max_new]
        if r.eos is not None:
            want = want[:want.index(r.eos) + 1]
        assert r.done and r.output == want, (case, i, r.output, want)


def test_sample_tokens_topk_edge_cases():
    """logits -> sample unit tests: k=1 is argmax; k >= vocab equals
    unmasked sampling; threshold ties stay eligible and deterministic;
    temperature <= 0 is greedy."""
    v = 11
    logits = jax.random.normal(jax.random.PRNGKey(0), (3, v))
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(3)])
    # k=1: the single retained logit must win at any temperature
    got = E.sample_tokens(logits, keys, temperature=2.5, top_k=1)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.argmax(logits, -1))
    )
    # k >= vocab: mask is a no-op — bitwise-identical draws
    full = E.sample_tokens(logits, keys, temperature=0.9, top_k=0)
    for k in (v, v + 7):
        np.testing.assert_array_equal(
            np.asarray(E.sample_tokens(logits, keys, temperature=0.9,
                                       top_k=k)),
            np.asarray(full),
        )
    # temperature <= 0 degenerates to greedy argmax
    got = E.sample_tokens(logits, keys, temperature=0.0, top_k=4)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.argmax(logits, -1))
    )
    # ties at the k-th value: both tied maxima stay eligible, draws are
    # deterministic per key, and across many keys both outcomes occur
    tied = jnp.zeros((1, v)).at[0, 2].set(5.0).at[0, 9].set(5.0)
    draws = set()
    for i in range(64):
        k1 = jax.random.PRNGKey(100 + i)[None]
        t1 = int(E.sample_tokens(tied, k1, temperature=1.0, top_k=1)[0])
        t2 = int(E.sample_tokens(tied, k1, temperature=1.0, top_k=1)[0])
        assert t1 == t2, "tied draw not deterministic for a fixed key"
        assert t1 in (2, 9), t1
        draws.add(t1)
    assert draws == {2, 9}, f"tie never explored both sides: {draws}"


# ---------------------------------------------------------------------------
# Seating: scatter/gather inverses, non-seated rows untouched
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ("qwen3_8b", "rwkv6_3b"))
def test_scatter_then_gather_roundtrips_and_preserves_others(built, name):
    model, params, _, prefill, _ = built[name]
    pool = model.init_cache(4)
    before = jax.tree.map(np.asarray, pool)
    prompts = jax.random.randint(
        jax.random.PRNGKey(2), (2, 3), 0, model.cfg.vocab
    )
    _, rows = prefill(params, prompts)
    src = jnp.asarray([0, 1], jnp.int32)
    dst = jnp.asarray([3, 1], jnp.int32)
    seated = seating.scatter_slots(pool, rows, src, dst)
    # gather returns exactly the seated rows, in order
    back = seating.gather_slots(seated, dst)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(rows)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # non-seated slots (0, 2) are bit-untouched
    untouched = seating.gather_slots(
        seated, jnp.asarray([0, 2], jnp.int32)
    )
    orig = seating.gather_slots(pool, jnp.asarray([0, 2], jnp.int32))
    for a, b in zip(jax.tree.leaves(untouched), jax.tree.leaves(orig)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the input pool itself was not mutated (pure function)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(pool)):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_scatter_slots_rejects_mismatched_trees(built):
    model, params, _, prefill, _ = built["qwen3_8b"]
    pool = model.init_cache(2)
    with pytest.raises(ValueError, match="leaves"):
        seating.scatter_slots(
            pool, {"not": jnp.zeros((1,))},
            jnp.asarray([0], jnp.int32), jnp.asarray([0], jnp.int32),
        )


# ---------------------------------------------------------------------------
# Enc-dec guard: typed error, actionable message
# ---------------------------------------------------------------------------


def test_encdec_guard_raises_typed_actionable_error():
    """`sharded.compile_decode` (and the engine / generate fronts) must
    reject whisper-family models with `EncDecUnsupportedError`, naming
    the model and saying what to do instead — so the open 'frames-aware
    prefill' ROADMAP item fails loudly, not by drifting."""
    cfg = configs.reduced("whisper_tiny")
    model = api.build_model(cfg, tp=1, max_seq=16)
    # avals suffice: the guard must fire before any real work
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    from repro.launch.mesh import make_smoke_mesh

    plan = SH.plan_decode(
        model, params, make_smoke_mesh(1, 1), batch_size=2
    )
    with pytest.raises(E.EncDecUnsupportedError) as ei:
        SH.compile_decode(model, plan)
    msg = str(ei.value)
    assert cfg.name in msg  # names the offending model
    assert "frames-aware prefill" in msg  # names the missing feature
    # actionable: tells the caller the working path to use today
    assert "model.prefill(params, tokens, frames)" in msg
    assert "decode_step" in msg

    with pytest.raises(E.EncDecUnsupportedError):
        E.Engine(model, params, batch_size=2)
    with pytest.raises(E.EncDecUnsupportedError):
        E.generate(model, params, jnp.zeros((1, 4), jnp.int32), max_new=1)


# ---------------------------------------------------------------------------
# Multi-device: the same properties on the 8-device data / TP meshes
# ---------------------------------------------------------------------------

multidevice = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices (scripts/ci.sh forces 8 host devices)",
)

# On a mesh the reference must be mesh-compiled too: an FSDP data axis
# re-gathers parameters, a model axis psums row-parallel contractions —
# either changes the fp reduction surface vs one device, and random-init
# logits are near-uniform enough that a bf16-level wiggle can flip a
# greedy token (test_decode_multidevice pins the cases where it happens
# not to). The engine's contract is against `sharded_generate` on the
# SAME mesh: identical cells, identical placement, zero fp slack.
MESH_CASES = [
    ("qwen3_8b", (8, 1)),
    ("recurrentgemma_2b", (8, 1)),
    ("qwen3_8b", (4, 2)),
]


def _mesh_ref_cells(model, params, mesh):
    """`sharded_generate`'s compiled cells for an 8-row pool on `mesh`:
    refs below broadcast one prompt across all rows and read row 0, so
    the solo stream goes through the exact placement the engine uses."""
    plan = SH.plan_decode(model, params, mesh, batch_size=8)
    prefill, decode = SH.compile_decode(model, plan)
    placed = SH.place_params(params, plan)
    return plan, prefill, decode, placed


def _mesh_ref_stream(cells, req: E.Request) -> list:
    plan, prefill, decode, placed = cells
    s = int(req.prompt.shape[0])
    prompts = jax.device_put(
        jnp.broadcast_to(
            jnp.asarray(req.prompt, jnp.int32)[None], (8, s)
        ),
        plan.prompts,
    )
    logits, cache = prefill(placed, prompts)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = []
    for t in range(req.max_new):
        out.append(int(tok[0]))
        if req.eos is not None and out[-1] == req.eos:
            break
        if len(out) >= req.max_new:
            break
        pos = jax.device_put(
            jnp.full((8,), s + t, jnp.int32), plan.token
        )
        logits, cache = decode(
            placed, cache, jax.device_put(tok, plan.token), pos
        )
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return out


@pytest.mark.slow
@multidevice
@pytest.mark.parametrize("name,mesh_shape", MESH_CASES)
@settings(max_examples=2, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_sharded_engine_matches_sharded_generate_under_interleavings(
    built, name, mesh_shape, seed
):
    """The mesh-placed engine — batched sharded prefill admission,
    scatter seating under explicit shardings, recurrent caches included
    — stays token-for-token identical to the `sharded_generate` cells
    on the same mesh, under random interleavings."""
    from repro.launch.mesh import make_smoke_mesh

    model, params, _, _, _ = built[name]
    mesh = make_smoke_mesh(*mesh_shape)
    cells = _mesh_ref_cells(model, params, mesh)
    rng = np.random.default_rng(seed)
    reqs = _make_requests(model.cfg, rng, 4)
    eng = SH.ShardedEngine(model, params, batch_size=8, mesh=mesh)
    _drive_random_interleaving(eng, reqs, rng)
    for r in reqs:
        assert r.done, r.uid
        ref = _mesh_ref_stream(cells, r)
        assert r.output == ref, (name, mesh_shape, r.uid, r.output, ref)
    assert all(s is None for s in eng._slots)
    assert not bool(eng.active.any())


@pytest.mark.slow
@multidevice
@pytest.mark.parametrize("mesh_shape", [(8, 1), (4, 2)])
def test_sharded_engine_reads_tokens_once_per_tick(built, mesh_shape):
    """`ShardedEngine` inherits the engine's single read of a tick's
    tokens: with all 8 slots occupied, a tick adds exactly one host read
    (the batch-sharded tokens gathered in one transfer), an admission
    group one more, and every stream still matches the
    `sharded_generate` cells on the same mesh."""
    from repro.launch.mesh import make_smoke_mesh

    model, params, _, _, _ = built["qwen3_8b"]
    mesh = make_smoke_mesh(*mesh_shape)
    cells = _mesh_ref_cells(model, params, mesh)
    reqs = [
        E.Request(uid=i, prompt=jax.random.randint(
            jax.random.PRNGKey(1000 + i), (3,), 0, model.cfg.vocab
        ), max_new=5)
        for i in range(8)
    ]
    eng = SH.ShardedEngine(model, params, batch_size=8, mesh=mesh)
    for r in reqs:
        eng.submit(r)
    for n in range(1, 4):
        assert eng.tick() == 8
        assert eng.host_reads == 1 + n
    eng.run(max_ticks=10)
    for r in reqs:
        assert r.done
        ref = _mesh_ref_stream(cells, r)
        assert r.output == ref, (mesh_shape, r.uid, r.output, ref)


@pytest.mark.slow
@multidevice
def test_sharded_engine_batched_recurrent_on_data_mesh(built):
    """Acceptance: recurrent-cache models decode through ShardedEngine
    at batch_size > 1 on the 8-device data mesh, matching
    `sharded_generate` (itself pinned to the single-device path in
    test_decode_multidevice) token-for-token."""
    from repro.launch.mesh import make_smoke_mesh

    model, params, _, _, _ = built["recurrentgemma_2b"]
    mesh = make_smoke_mesh(8, 1)
    prompts = jax.random.randint(
        jax.random.PRNGKey(11), (8, 4), 0, model.cfg.vocab
    )
    ref = np.asarray(SH.sharded_generate(
        model, params, prompts, mesh=mesh, max_new=4
    ))
    eng = SH.ShardedEngine(model, params, batch_size=8, mesh=mesh)
    reqs = [
        E.Request(uid=i, prompt=prompts[i], max_new=4) for i in range(8)
    ]
    for r in reqs:
        eng.submit(r)
    eng.run(max_ticks=30)
    for i, r in enumerate(reqs):
        assert r.done
        assert r.output == ref[i].tolist(), (i, r.output, ref[i].tolist())


@pytest.mark.slow
@multidevice
def test_sharded_sampling_reproducible_on_data_mesh(built):
    """Per-request folded keys survive sharding: sampled streams on the
    8-device mesh are reproducible across runs and across seat order."""
    from repro.launch.mesh import make_smoke_mesh

    model, params, _, _, _ = built["qwen3_8b"]
    mesh = make_smoke_mesh(8, 1)
    key = jax.random.PRNGKey(13)

    def run(order):
        eng = SH.ShardedEngine(
            model, params, batch_size=8, mesh=mesh, greedy=False,
            temperature=0.8, top_k=5, key=key,
        )
        reqs = {
            uid: E.Request(uid=uid, prompt=jax.random.randint(
                jax.random.PRNGKey(1000 + uid), (3,), 0, model.cfg.vocab
            ), max_new=3)
            for uid in order
        }
        for uid in order:
            eng.submit(reqs[uid])
        eng.run(max_ticks=20)
        return {uid: r.output for uid, r in reqs.items()}

    a = run([0, 1, 2])
    b = run([0, 1, 2])
    c = run([2, 0, 1])
    assert a == b and a == c
