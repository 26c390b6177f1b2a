"""Async serving frontend (`repro.serve.frontend`): wire framing,
deterministic token-bucket admission, the shedding contract as a
property over the in-process transport — every LM request gets exactly
one terminal outcome (completed XOR typed rejection), rejections only
when an admission rate is configured, URGENT segments never shed or
deferred at any load — and a loopback-socket end-to-end run whose
client-minted request ids join lineages across the transport hop.
"""

import asyncio

import jax
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import configs, obs
from repro.models import api
from repro.obs import lineage
from repro.serve import engine as E
from repro.serve.frontend import (
    Frontend,
    FrontendConfig,
    InProcClient,
    SocketClient,
    TokenBucket,
    encode_frame,
    read_frame,
)

PROMPT_LEN = 4
MAX_NEW = 3


@pytest.fixture(scope="module")
def built():
    """Shared model/params: each test gets a fresh engine but the jit
    caches are shared, so per-test warmup is cheap."""
    cfg = configs.reduced("qwen3_8b")
    model = api.build_model(cfg, tp=1, max_seq=PROMPT_LEN + MAX_NEW + 2)
    params = model.init(jax.random.PRNGKey(0))

    def make_engine():
        return E.Engine(model, params, batch_size=2)

    def prompts(n):
        toks = jax.random.randint(
            jax.random.PRNGKey(1), (n, PROMPT_LEN), 0, cfg.vocab
        )
        return [[int(t) for t in toks[i]] for i in range(n)]

    return make_engine, prompts


# -- wire framing -----------------------------------------------------------


def test_frame_roundtrip():
    msg = {"type": "lm", "uid": 3, "prompt": [1, 2],
           "nested": {"a": [1.5, None, "x"]}}

    async def go():
        reader = asyncio.StreamReader()
        reader.feed_data(encode_frame(msg) + encode_frame({"type": "drain"}))
        reader.feed_eof()
        return (await read_frame(reader), await read_frame(reader),
                await read_frame(reader))

    m1, m2, m3 = asyncio.run(go())
    assert m1 == msg
    assert m2 == {"type": "drain"}
    assert m3 is None  # clean EOF at a frame boundary


def test_frame_size_cap():
    with pytest.raises(ValueError, match="exceeds"):
        encode_frame({"x": "a" * 100}, max_frame_bytes=16)

    async def go():
        reader = asyncio.StreamReader()
        reader.feed_data(encode_frame({"x": "a" * 100}))
        return await read_frame(reader, max_frame_bytes=16)

    with pytest.raises(ValueError, match="exceeds"):
        asyncio.run(go())


# -- token bucket -----------------------------------------------------------


def test_token_bucket_burst_exact():
    """Back-to-back offers against a full bucket admit exactly
    floor(burst); refill is rate * elapsed, clamped at burst."""
    t = [0.0]
    b = TokenBucket(2.0, 3.0, clock=lambda: t[0])
    assert [b.try_take() for _ in range(5)] == [True] * 3 + [False] * 2
    t[0] += 1.0  # refills 2 tokens
    assert [b.try_take() for _ in range(3)] == [True, True, False]
    t[0] += 100.0  # clamped at burst depth, not rate * 100
    assert [b.try_take() for _ in range(4)] == [True] * 3 + [False]


def test_token_bucket_validates():
    with pytest.raises(ValueError):
        TokenBucket(0.0, 4.0)
    with pytest.raises(ValueError):
        TokenBucket(1.0, 0.5)


# -- shedding contract (property, in-process transport) ---------------------


@settings(max_examples=4, deadline=None)
@given(
    n_lm=st.integers(min_value=4, max_value=10),
    burst=st.integers(min_value=1, max_value=4),
    gated=st.booleans(),
)
def test_inproc_shedding_property(built, n_lm, burst, gated):
    """For any offered burst: exactly one terminal outcome per LM
    request; with an admission rate configured (near-zero refill,
    integer burst b) exactly min(n, b) complete and the rest carry the
    typed `admission_rate` rejection; with no rate nothing is ever
    rejected; URGENT segments are enqueued at any load while over-rate
    ROUTINE segments defer (never drop)."""
    make_engine, prompts = built
    fcfg = FrontendConfig(
        admission_rate_rps=(1e-9 if gated else None),
        admission_burst=float(burst),
        stream_rate_rps=(1e-9 if gated else None),
        stream_burst=1.0,
    )

    async def go():
        fe = Frontend(engine=make_engine(), n_patients=4, cfg=fcfg)
        fe.warm(PROMPT_LEN)
        await fe.start(host=None)
        client = InProcClient(fe)
        futs = [
            await client.send_lm(uid=i, prompt=p, max_new=MAX_NEW)
            for i, p in enumerate(prompts(n_lm))
        ]
        ufuts = [
            await client.send_segment(patient=0, seq=s, urgent=True)
            for s in range(3)
        ]
        rfuts = [
            await client.send_segment(patient=p, seq=0)
            for p in (1, 2, 3)
        ]
        res = [await asyncio.wait_for(f, 60.0) for f in futs]
        uacks = [await asyncio.wait_for(f, 60.0) for f in ufuts]
        racks = [await asyncio.wait_for(f, 60.0) for f in rfuts]
        stats = (await client.drain())["stats"]
        await fe.stop()
        return res, uacks, racks, stats

    res, uacks, racks, stats = asyncio.run(go())

    # exactly one terminal outcome: the reply future resolves once,
    # with either tokens (completed) or a typed reason (rejected)
    assert len(res) == n_lm
    completed = [r for r in res if r["status"] == "completed"]
    rejected = [r for r in res if r["status"] == "rejected"]
    assert len(completed) + len(rejected) == n_lm
    for r in completed:
        assert len(r["tokens"]) == MAX_NEW and "reason" not in r
    for r in rejected:
        assert r["reason"] == "admission_rate" and "tokens" not in r
    assert stats.get("lm_completed", 0) == len(completed)
    assert stats.get("lm_rejected", 0) == len(rejected)
    if gated:
        # bucket starts full at depth `burst`, refill ~1e-9/s: a
        # back-to-back burst admits exactly min(n, burst)
        assert len(completed) == min(n_lm, burst)
    else:
        assert not rejected

    # URGENT always lands; ROUTINE past the bucket defers, never drops
    assert all(a["status"] == "enqueued" for a in uacks)
    assert all(a["status"] in ("enqueued", "deferred") for a in racks)
    if gated:
        assert sum(a["status"] == "deferred" for a in racks) == 2
    # drain force-released every deferral into the scheduler and packed
    # the queue dry: nothing lost
    assert stats["deferred_pending"] == 0
    assert stats["sched_enqueued_total"] == stats["sched_packed_total"]
    assert stats["sched_enqueued_total"] == len(uacks) + len(racks)


# -- loopback socket end-to-end ---------------------------------------------


def test_socket_loopback_lineage(built):
    """Client-minted request ids survive the wire: a completed LM
    request and a streamed segment sent over a real loopback socket
    each join a lineage of >= 4 distinct hops including the
    transport's."""
    make_engine, prompts = built
    fe = Frontend(engine=make_engine(), n_patients=2,
                  cfg=FrontendConfig())
    fe.warm(PROMPT_LEN)  # outside the trace: warm uids aren't lineages
    saved = obs.get()
    tel = obs.configure(enabled=True)
    try:
        async def go():
            host, port = await fe.start("127.0.0.1", 0)
            client = await SocketClient.connect(host, port)
            f1 = await client.send_lm(
                uid=0, prompt=prompts(1)[0], max_new=MAX_NEW
            )
            f2 = await client.send_segment(patient=1, seq=0)
            r1 = await asyncio.wait_for(f1, 60.0)
            a1 = await asyncio.wait_for(f2, 60.0)
            await client.drain()
            await client.close()
            await fe.stop()
            return r1, a1

        r1, a1 = asyncio.run(go())
        events = tel.tracer.events()
    finally:
        obs.install(saved)

    assert r1["status"] == "completed" and len(r1["tokens"]) == MAX_NEW
    assert a1["status"] == "enqueued"
    joined = lineage.assert_joined(events, min_hops=4)
    serve_names = {h.name for h in joined["serve:0"]}
    assert {"frontend/ingress", "serve/submit", "serve/finish",
            "frontend/reply"} <= serve_names
    stream_names = {h.name for h in joined["stream:1:0"]}
    assert {"frontend/ingress", "frontend/ack",
            "stream/enqueue"} <= stream_names
    cp = lineage.critical_path(joined["serve:0"])
    assert cp["hop_names"][0] == "frontend/ingress"
    assert cp["hop_names"][-1] == "frontend/reply"
    assert cp["total_s"] > 0


# -- engine failures at submit ----------------------------------------------


class _RaisingEngine:
    """An engine whose `submit` raises `exc`: validation errors must
    come back as typed rejections, anything else must end the engine
    thread, reject what is pending as `engine_failed`, and surface from
    `stop()` instead of reading as a client error."""

    _queue = ()
    model = type("M", (), {"cfg": configs.reduced("qwen3_8b")})

    def __init__(self, exc: Exception):
        self.exc = exc

    def submit(self, req) -> None:
        raise self.exc


@pytest.mark.parametrize("exc,surfaces", [
    (ValueError("request 0: empty prompt"), False),
    (TypeError("prompt must be int32"), False),
    (RuntimeError("device lost"), True),
])
def test_engine_submit_failure_routing(exc, surfaces):
    async def go():
        fe = Frontend(engine=_RaisingEngine(exc))
        await fe.start(host=None)
        client = InProcClient(fe)
        fut = await client.send_lm(uid=0, prompt=[1, 2, 3], max_new=2)
        res = await asyncio.wait_for(fut, 10.0)
        if surfaces:
            # a request arriving after the driver died is refused at once
            late = await asyncio.wait_for(
                await client.send_lm(uid=1, prompt=[1], max_new=2), 10.0
            )
            with pytest.raises(RuntimeError, match="driver thread died") as ei:
                await fe.stop()
            assert ei.value.__cause__ is exc
            return res, late
        await fe.stop()
        return res, None

    res, late = asyncio.run(go())
    assert res["status"] == "rejected"
    if surfaces:
        for r in (res, late):
            assert r["reason"] == "engine_failed"
            assert "device lost" in r["detail"]
    else:
        assert res["reason"] == "invalid"
        assert str(exc) in res["detail"]


def test_out_of_vocab_tokens_rejected_at_ingress(built):
    """Token ids outside [0, vocab), and uids or segment seqs outside
    the uint32 PRNG-key domain — JSON integers are unbounded, so one can
    exceed int32 — come back as `invalid` rejections, and the driver
    keeps serving: the next well-formed request completes."""
    make_engine, prompts = built
    vocab = configs.reduced("qwen3_8b").vocab
    good = prompts(1)[0]
    bad = [(0, [2**40], None), (1, [-1], None), (2, [1, vocab], None),
           (3, good, vocab), (2**40, good, None), (-1, good, None)]

    async def go():
        fe = Frontend(engine=make_engine(), n_patients=2)
        fe.warm(PROMPT_LEN)
        await fe.start(host=None)
        client = InProcClient(fe)
        futs = [await client.send_lm(uid=u, prompt=p, max_new=MAX_NEW,
                                     eos=eos)
                for u, p, eos in bad]
        segs = [await client.send_segment(0, s) for s in (2**40, -1)]
        futs.append(await client.send_lm(
            uid=len(bad), prompt=good, max_new=MAX_NEW
        ))
        res = [await asyncio.wait_for(f, 60.0) for f in futs]
        seg_res = [await asyncio.wait_for(f, 60.0) for f in segs]
        await fe.stop()  # raises if the driver died
        return res, seg_res

    res, seg_res = asyncio.run(go())
    for r in res[:-1] + seg_res:
        assert r["status"] == "rejected" and r["reason"] == "invalid"
        assert "outside" in r["detail"]
    assert res[-1]["status"] == "completed"
    assert len(res[-1]["tokens"]) == MAX_NEW
