"""Serving: prefill+decode == teacher-forced; engine; quantized serving."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import api
from repro.models.transformer import forward_train
from repro.serve import engine as E

# one representative per family (full matrix runs in test_archs_smoke)
FAMILIES = ["qwen3_8b", "olmoe_1b_7b", "recurrentgemma_2b", "rwkv6_3b",
            "whisper_tiny", "llama4_scout_17b_a16e"]


@pytest.mark.parametrize("name", FAMILIES)
def test_decode_matches_teacher_forced(name):
    cfg = configs.reduced(name)
    S, B, NEW = 12, 2, 3
    model = api.build_model(cfg, tp=1, max_seq=S + NEW + 1)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    newt = jax.random.randint(jax.random.PRNGKey(2), (B, NEW), 0, cfg.vocab)
    allt = jnp.concatenate([toks, newt], 1)
    if cfg.is_enc_dec:
        frames = jax.random.normal(
            jax.random.PRNGKey(3), (B, cfg.enc_seq, cfg.d_model)
        )
        from repro.models import whisper as W

        last, cache = model.prefill(params, toks, frames)
        enc = W.encode(params, frames, cfg, model.dims)
        full = W.decode_train(params, allt, enc, cfg, model.dims)
    else:
        last, cache = model.prefill(params, toks)
        full, _ = forward_train(params, allt, cfg, model.dims)
    np.testing.assert_allclose(last, full[:, S - 1], rtol=3e-2, atol=3e-2)
    for t in range(NEW):
        pos = jnp.full((B,), S + t, jnp.int32)
        lg, cache = model.decode_step(params, cache, allt[:, S + t], pos)
        np.testing.assert_allclose(lg, full[:, S + t], rtol=4e-2, atol=4e-2)


def test_generate_greedy_deterministic():
    cfg = configs.reduced("qwen3_8b")
    model = api.build_model(cfg, tp=1, max_seq=40)
    params = model.init(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab)
    out1 = E.generate(model, params, prompts, max_new=6)
    out2 = E.generate(model, params, prompts, max_new=6)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert out1.shape == (2, 6)


def test_engine_slots_and_recycling():
    cfg = configs.reduced("qwen3_8b")
    model = api.build_model(cfg, tp=1, max_seq=64)
    params = model.init(jax.random.PRNGKey(0))
    eng = E.Engine(model, params, batch_size=2)
    reqs = [
        E.Request(uid=i,
                  prompt=jax.random.randint(
                      jax.random.PRNGKey(i), (5,), 0, cfg.vocab),
                  max_new=4)
        for i in range(3)  # 3 requests, 2 slots -> forces recycling
    ]
    for r in reqs:
        eng.submit(r)
    eng.run(max_ticks=50)
    for r in reqs:
        assert r.done and len(r.output) == 4


@pytest.mark.parametrize("k", [1, 3])
def test_engine_host_reads_counted(k):
    """`Engine.host_reads` counts the tick's device-to-host reads:
    admission reads each seated request's first token and its prompt's
    last token, and each tick one token per occupied slot — a tick with
    k occupied slots and no admission adds k."""
    from repro.serve.paging import PagingConfig, validate_page_size

    cfg = configs.reduced("qwen3_8b")
    model = api.build_model(cfg, tp=1, max_seq=32)
    params = model.init(jax.random.PRNGKey(0))
    span = validate_page_size(8, model.attn_capacities())
    eng = E.Engine(model, params, batch_size=4,
                   paging=PagingConfig(8, 4 * span + 1))
    for uid in range(k):
        eng.submit(E.Request(
            uid=uid, prompt=jax.random.randint(
                jax.random.PRNGKey(uid), (5,), 0, cfg.vocab),
            max_new=8))
    eng.tick()
    assert eng.host_reads == 2 * k + k
    for n in range(2, 5):
        assert eng.tick() == k
        assert eng.host_reads == 2 * k + n * k


def test_engine_eos_on_first_token_recycles_slot():
    """Regression: a request finishing on the same tick it was admitted
    (EOS as its very first generated token) must not leak its slot —
    later queued requests still get seated and completed."""
    cfg = configs.reduced("qwen3_8b")
    model = api.build_model(cfg, tp=1, max_seq=64)
    params = model.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(9), (5,), 0, cfg.vocab)
    # discover the greedy first token for this prompt
    probe = E.Request(uid=0, prompt=prompt, max_new=2)
    eng = E.Engine(model, params, batch_size=1)
    eng.submit(probe)
    eng.tick()
    first = probe.output[0]

    eng = E.Engine(model, params, batch_size=1)
    eos_reqs = [
        E.Request(uid=i, prompt=prompt, max_new=8, eos=first)
        for i in range(1, 4)
    ]
    tail = E.Request(uid=9, prompt=prompt, max_new=3)
    for r in (*eos_reqs, tail):
        eng.submit(r)
    eng.run(max_ticks=30)
    for r in eos_reqs:
        assert r.done and r.output == [first], r
    assert tail.done and len(tail.output) == 3
    # pool fully recycled: no occupied slots, no active flags
    assert all(s is None for s in eng._slots)
    assert not bool(eng.active.any())


def test_engine_coadmission_does_not_corrupt_seated_slots():
    """Regression: admitting request B while A is seated re-decodes the
    whole pool during B's prefill; A's cache must see an idempotent
    replay of its committed state, not its pending token — A's output
    must match a solo run."""
    cfg = configs.reduced("qwen3_8b")
    model = api.build_model(cfg, tp=1, max_seq=64)
    params = model.init(jax.random.PRNGKey(0))
    prompt_a = jax.random.randint(jax.random.PRNGKey(1), (5,), 0, cfg.vocab)
    prompt_b = jax.random.randint(jax.random.PRNGKey(2), (5,), 0, cfg.vocab)

    solo = E.Engine(model, params, batch_size=2)
    ra = E.Request(uid=0, prompt=prompt_a, max_new=6)
    solo.submit(ra)
    solo.run(max_ticks=20)

    duo = E.Engine(model, params, batch_size=2)
    ra2 = E.Request(uid=1, prompt=prompt_a, max_new=6)
    rb = E.Request(uid=2, prompt=prompt_b, max_new=6)
    duo.submit(ra2)
    duo.submit(rb)
    duo.run(max_ticks=20)
    assert ra2.output == ra.output, (ra2.output, ra.output)


def test_quantized_serving_logits_close():
    """int8 weight-only serving keeps the logit surface close to the
    dense path (argmax agreement on a random-init tiny model is noise —
    the near-uniform logits flip on tiny perturbations — so we assert
    logit correlation, which is what transfers to trained models)."""
    cfg = configs.reduced("qwen3_8b")
    model = api.build_model(cfg, tp=1, max_seq=40)
    params = model.init(jax.random.PRNGKey(0))
    qparams = E.quantize_for_serving(params, bits=8)
    # format check: projections packed, embeddings dense
    blk = qparams["blocks"]["pos0"]
    assert "packed" in blk["mix"]["wq"]
    assert "w" in qparams["embed"]
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                 cfg.vocab)
    ld, _ = jax.jit(model.prefill)(params, prompts)
    lq, _ = jax.jit(model.prefill)(qparams, prompts)
    a = np.asarray(ld, np.float64).ravel()
    b = np.asarray(lq, np.float64).ravel()
    corr = np.corrcoef(a, b)[0, 1]
    assert corr > 0.98, corr


def test_quantized_params_smaller():
    cfg = configs.reduced("qwen3_8b")
    model = api.build_model(cfg, tp=1, max_seq=16)
    params = model.init(jax.random.PRNGKey(0))
    def nbytes(t):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t)
                   if hasattr(x, "dtype"))
    # projection weights (the quantized targets) shrink ~8x at int4;
    # embeddings/norms stay dense, so compare the blocks subtree.
    q4 = E.quantize_for_serving(params, bits=4)
    q8 = E.quantize_for_serving(params, bits=8)
    assert nbytes(q4["blocks"]) < 0.26 * nbytes(params["blocks"])
    assert nbytes(q8["blocks"]) < 0.45 * nbytes(params["blocks"])
    assert nbytes(q4) < nbytes(q8) < nbytes(params)


def test_va_service_end_to_end():
    from repro.configs import va_cnn
    from repro.core import compiler, vadetect
    from repro.data import iegm
    from repro.serve.va_service import VAService

    params = vadetect.init(jax.random.PRNGKey(0), va_cnn.CONFIG)
    program = compiler.compile_model(params, va_cnn.CONFIG)
    svc = VAService(program, va_cnn.CONFIG)
    batch = iegm.synth_diagnosis_batch(jax.random.PRNGKey(1), 4)
    out = svc.diagnose_batch(batch["signal"])
    assert len(out) == 4
    assert all(len(d.segment_preds) == 6 for d in out)
    assert out[0].chip_latency_us > 0


def test_submit_guards_invalid_and_duplicate_uid():
    """`submit` rejects max_new <= 0 and a uid already in flight with
    actionable errors (a duplicate would clobber the live request's
    TTFT accounting and collide its sampling stream); uid reuse AFTER
    completion stays legal — the frontend and warmup paths rely on it."""
    cfg = configs.reduced("qwen3_8b")
    model = api.build_model(cfg, tp=1, max_seq=32)
    params = model.init(jax.random.PRNGKey(0))
    eng = E.Engine(model, params, batch_size=2)

    def req(uid, max_new=3):
        return E.Request(
            uid=uid,
            prompt=jax.random.randint(
                jax.random.PRNGKey(uid), (4,), 0, cfg.vocab
            ),
            max_new=max_new,
        )

    with pytest.raises(ValueError, match="max_new must be >= 1"):
        eng.submit(req(0, max_new=0))
    with pytest.raises(ValueError, match="max_new must be >= 1"):
        eng.submit(req(0, max_new=-2))

    r = req(1)
    eng.submit(r)
    with pytest.raises(ValueError, match="uid already in flight"):
        eng.submit(req(1))
    eng.run(max_ticks=50)
    assert r.done and len(r.output) == 3

    r2 = req(1)  # same uid, prior request finished: legal reuse
    eng.submit(r2)
    eng.run(max_ticks=50)
    assert r2.done and len(r2.output) == 3
