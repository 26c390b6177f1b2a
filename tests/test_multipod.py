"""Compressed multi-pod DP training: two-stage reduction properties,
error-buffer checkpointing, interrupted-vs-uninterrupted equivalence,
and the launcher composition.

Multi-device tests need `scripts/ci.sh` (8 forced host devices); on a
single-device host they skip. The hypothesis property tests sample pod
counts from the divisors of whatever device count is available, so the
n=1 degenerate case is exercised everywhere.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro import configs, optim
from repro.data import lm
from repro.dist import compression as C
from repro.models import api
from repro.train import checkpoint as ckpt
from repro.train import fault, trainer

multidevice = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >=2 devices (scripts/ci.sh forces 8 host devices)",
)
eight_devices = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices (scripts/ci.sh forces 8 host devices)",
)

_POD_COUNTS = [n for n in (1, 2, 4, 8) if n <= jax.device_count()]


def _pod_mesh(n):
    return jax.make_mesh(
        (n,), ("pod",), devices=jax.devices()[:n],
        axis_types=(jax.sharding.AxisType.Auto,),
    )


# ---------------------------------------------------------------------------
# two-stage reduction: hypothesis properties
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _two_stage_reduce(n, sizes):
    """Jitted shard_map running the two-stage reduction for a dict tree
    with leaves of the given flat sizes over an n-pod mesh. Inputs
    carry a leading (n,) pod dim."""
    mesh = _pod_mesh(n)

    def body(g, e1, e2):
        sq = lambda t: jax.tree.map(lambda x: x[0], t)  # noqa: E731
        ex = lambda t: jax.tree.map(lambda x: x[None], t)  # noqa: E731
        m, a, b = C.two_stage_psum_mean(sq(g), sq(e1), sq(e2), "pod")
        return m, ex(a), ex(b)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("pod"), P("pod"), P("pod")),
        out_specs=(P(), P("pod"), P("pod")),
        check_vma=False,
    ))


def _rand_tree(key, n, sizes, mag):
    ks = jax.random.split(key, len(sizes))
    return {
        f"l{i}": jax.random.normal(ks[i], (n, s)) * mag
        for i, s in enumerate(sizes)
    }


@settings(max_examples=8, deadline=None)
@given(
    n=st.sampled_from(_POD_COUNTS),
    s0=st.integers(1, 40),
    s1=st.integers(1, 200),
    seed=st.integers(0, 2**16),
    logmag=st.integers(-2, 3),
)
def test_two_stage_mean_within_one_shot_bound(n, s0, s1, seed, logmag):
    """From zero buffers, the two-stage dequantized mean is within the
    composed one-shot quantization bound of the f32 mean: each stage
    contributes at most half its scale, and both scales are bounded by
    amax/127 (stage 2's by a hair more — its operand is the stage-1
    mean plus its own error, bounded by amax*(1 + 1/254))."""
    mag = 10.0 ** logmag
    sizes = (s0, s1)
    g = _rand_tree(jax.random.PRNGKey(seed), n, sizes, mag)
    e1 = jax.tree.map(jnp.zeros_like, g)
    e2 = {
        k: jnp.zeros((n, C.two_stage_shard_len(v.shape[1], n)))
        for k, v in g.items()
    }
    mean, _, _ = _two_stage_reduce(n, sizes)(g, e1, e2)
    for k in g:
        amax = float(jnp.abs(g[k]).max())
        bound = amax / 127.0 * 1.05 + 1e-7
        err = float(jnp.abs(mean[k] - jnp.mean(g[k], 0)).max())
        assert err <= bound, (k, err, bound, n, sizes, mag)


@settings(max_examples=6, deadline=None)
@given(
    n=st.sampled_from(_POD_COUNTS),
    s0=st.integers(1, 40),
    s1=st.integers(1, 200),
    steps=st.integers(2, 5),
    seed=st.integers(0, 2**16),
    logmag=st.integers(-2, 2),
)
def test_two_stage_error_feedback_telescopes(n, s0, s1, steps, seed,
                                             logmag):
    """Over multi-step sequences both error-feedback stages telescope:
    sum of the returned means + pod-mean of err1 + the assembled err2
    shards reconstructs the sum of true f32 means (losslessness over
    time, the property that makes compressed SGD unbiased)."""
    mag = 10.0 ** logmag
    sizes = (s0, s1)
    fn = _two_stage_reduce(n, sizes)
    key = jax.random.PRNGKey(seed)
    e1 = {f"l{i}": jnp.zeros((n, s)) for i, s in enumerate(sizes)}
    e2 = {
        f"l{i}": jnp.zeros((n, C.two_stage_shard_len(s, n)))
        for i, s in enumerate(sizes)
    }
    sent = {f"l{i}": jnp.zeros(s) for i, s in enumerate(sizes)}
    true = {f"l{i}": jnp.zeros(s) for i, s in enumerate(sizes)}
    for t in range(steps):
        g = _rand_tree(jax.random.fold_in(key, t), n, sizes, mag)
        mean, e1, e2 = fn(g, e1, e2)
        sent = jax.tree.map(jnp.add, sent, mean)
        true = jax.tree.map(
            jnp.add, true, jax.tree.map(lambda x: jnp.mean(x, 0), g)
        )
    for i, s in enumerate(sizes):
        k = f"l{i}"
        resid = jnp.mean(e1[k], 0) + e2[k].reshape(-1)[:s]
        np.testing.assert_allclose(
            np.asarray(sent[k] + resid), np.asarray(true[k]),
            rtol=2e-4, atol=2e-4 * mag * steps + 1e-6,
        )


# ---------------------------------------------------------------------------
# non-finite gradient parity across the reduction paths
# ---------------------------------------------------------------------------


@multidevice
def test_nonfinite_injection_parity_across_paths():
    """A loss-spike pod emitting inf/NaN is zeroed identically by every
    reduction path (compress=False included — the fair-ablation guard);
    `finite_guard=False` reproduces the raw IEEE propagation."""
    n = jax.device_count()
    mesh = _pod_mesh(n)
    k = 64
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (n, k))}
    bad = g["w"].at[0, 0].set(jnp.inf).at[0, 1].set(-jnp.inf)
    bad = bad.at[0, 2].set(jnp.nan)
    g = {"w": bad}
    # expected: the injecting pod's non-finite entries contribute 0
    zeroed = jnp.where(jnp.isfinite(bad), bad, 0.0)
    expected = jnp.mean(zeroed, 0)
    amax = float(jnp.abs(zeroed).max())

    def run_gather():
        e = {"w": jnp.zeros((n, k))}

        def body(gg, ee):
            sq = lambda t: jax.tree.map(lambda x: x[0], t)  # noqa: E731
            m, ne = C.compressed_psum_mean(sq(gg), sq(ee), "pod")
            return m, jax.tree.map(lambda x: x[None], ne)

        f = jax.shard_map(
            body, mesh=mesh, in_specs=(P("pod"), P("pod")),
            out_specs=(P(), P("pod")), check_vma=False,
        )
        return f(g, e)[0]["w"]

    def run_two_stage():
        e1 = {"w": jnp.zeros((n, k))}
        e2 = {"w": jnp.zeros((n, C.two_stage_shard_len(k, n)))}
        return _two_stage_reduce(n, (k,))(
            {"l0": g["w"]}, {"l0": e1["w"]}, {"l0": e2["w"]}
        )[0]["l0"]

    def run_uncompressed(**kw):
        f = jax.shard_map(
            lambda gg: C.uncompressed_psum_mean(
                jax.tree.map(lambda x: x[0], gg), "pod", **kw
            ),
            mesh=mesh, in_specs=(P("pod"),), out_specs=P(),
            check_vma=False,
        )
        return f(g)["w"]

    for name, out in (
        ("gather", run_gather()),
        ("two_stage", run_two_stage()),
        ("uncompressed", run_uncompressed()),
    ):
        assert bool(jnp.isfinite(out).all()), name
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected),
            atol=2 * amax / 127.0 + 1e-6, err_msg=name,
        )
    raw = run_uncompressed(finite_guard=False)
    assert not bool(jnp.isfinite(raw).all())


# ---------------------------------------------------------------------------
# error-buffer checkpointing + interrupted-run equivalence
# ---------------------------------------------------------------------------


def _dp_setup(mesh, scheme, *, compress=True, seed=0):
    cfg = configs.reduced("qwen3_8b")
    model = api.build_model(cfg, tp=1, max_seq=32)
    params = model.init(jax.random.PRNGKey(seed))
    opt = optim.adamw(3e-3)
    state = trainer.init_state(params, opt)
    state["err"] = trainer.init_dp_err(
        params, mesh, scheme=scheme, compress=compress
    )
    step = jax.jit(trainer.make_dp_step_compressed(
        model.loss, opt, mesh, scheme=scheme, compress=compress
    ))
    stream = lm.TokenStream(batch=8, seq_len=16, vocab=cfg.vocab,
                            seed=seed)
    return cfg, model, state, step, stream


@multidevice
@pytest.mark.parametrize("scheme", ["gather", "two_stage"])
def test_err_buffers_checkpoint_roundtrip_bitwise(tmp_path, scheme):
    """The per-pod error buffers are part of state and round-trip
    bitwise — including DISTINCT per-pod residuals (the old replicated
    out-spec silently saved one pod's copy for all, breaking the
    telescoping identity on every restart)."""
    n = jax.device_count()
    mesh = _pod_mesh(n)
    _, _, state, step, stream = _dp_setup(mesh, scheme)
    for i in range(3):
        state, _ = step(state, stream.batch_at(i))
    e1 = np.asarray(jax.tree.leaves(state["err"]["s1"])[0])
    per_pod = np.abs(e1).sum(axis=tuple(range(1, e1.ndim)))
    assert np.ptp(per_pod) > 0, "pods should carry distinct residuals"

    ckpt.save(state, str(tmp_path), 3)
    restored, s = ckpt.restore(str(tmp_path), state)
    assert s == 3
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@multidevice
@pytest.mark.parametrize("scheme", ["gather", "two_stage"])
def test_interrupted_equals_uninterrupted(tmp_path, scheme):
    """Kill-and-resume mid-run reproduces the uninterrupted loss curve
    bitwise: the restored error buffers re-enter the quantizer exactly
    where the killed run left them."""
    n = jax.device_count()
    mesh = _pod_mesh(n)
    _, _, state0, step, stream = _dp_setup(mesh, scheme)

    def fresh():
        return jax.tree.map(
            lambda x: jnp.copy(x) if hasattr(x, "dtype") else x, state0
        )

    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    final1, hist1 = fault.run_training(
        step, fresh(), stream.batch_at, num_steps=10,
        ckpt_dir=d1, ckpt_every=4, log_every=0,
    )
    # interrupted twin: stop at 6 (kill), then resume to 10
    fault.run_training(
        step, fresh(), stream.batch_at, num_steps=6,
        ckpt_dir=d2, ckpt_every=4, log_every=0,
    )
    final2, hist2b = fault.run_training(
        step, fresh(), stream.batch_at, num_steps=10,
        ckpt_dir=d2, ckpt_every=4, log_every=0,
    )
    assert hist2b[0]["step"] == 6
    tail1 = [h["loss"] for h in hist1 if h["step"] >= 6]
    tail2 = [h["loss"] for h in hist2b]
    assert tail1 == tail2  # bitwise: same floats, not approx
    for a, b in zip(jax.tree.leaves(final1), jax.tree.leaves(final2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# the composed launcher path (pjit in-pod x compressed pod axis)
# ---------------------------------------------------------------------------


def _multipod_setup(scheme, *, compress=True, seed=0):
    from repro.launch.mesh import make_multipod_mesh

    cfg = configs.reduced("qwen3_8b")
    mesh = make_multipod_mesh("2x2x2")
    model = api.build_model(cfg, tp=1, max_seq=32)
    opt = optim.adamw(3e-3)

    def fresh_state():
        params = model.init(jax.random.PRNGKey(seed))
        state = trainer.init_state(params, opt)
        state["err"] = trainer.init_dp_err(
            params, mesh, scheme=scheme, compress=compress
        )
        return state

    state = fresh_state()
    py_step, s_shard = trainer.make_multipod_train_step(
        model.loss, opt, cfg, mesh, jax.eval_shape(lambda: state),
        scheme=scheme, compress=compress,
    )
    stream = lm.TokenStream(batch=8, seq_len=16, vocab=cfg.vocab,
                            seed=seed)
    return fresh_state, py_step, s_shard, stream


@eight_devices
@pytest.mark.slow
def test_multipod_kill_resume_bitwise(tmp_path):
    """Acceptance: the composed multi-pod step (in-pod pjit x pod-axis
    compressed reduction) under `fault.run_training` — kill-and-resume
    mid-run reproduces the uninterrupted loss curve bitwise, error
    buffers restored under the trainer's state shardings."""
    fresh_state, py_step, s_shard, stream = _multipod_setup("two_stage")
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    final1, hist1 = fault.run_training(
        py_step, fresh_state(), stream.batch_at, num_steps=8,
        ckpt_dir=d1, ckpt_every=3, log_every=0,
        restore_shardings=s_shard,
    )
    fault.run_training(
        py_step, fresh_state(), stream.batch_at, num_steps=5,
        ckpt_dir=d2, ckpt_every=3, log_every=0,
        restore_shardings=s_shard,
    )
    final2, hist2 = fault.run_training(
        py_step, fresh_state(), stream.batch_at, num_steps=8,
        ckpt_dir=d2, ckpt_every=3, log_every=0,
        restore_shardings=s_shard,
    )
    assert hist2[0]["step"] == 5
    tail1 = [h["loss"] for h in hist1 if h["step"] >= 5]
    assert tail1 == [h["loss"] for h in hist2]
    for a, b in zip(jax.tree.leaves(final1), jax.tree.leaves(final2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@eight_devices
@pytest.mark.slow
def test_multipod_fault_injection_recovers(tmp_path):
    """An injected mid-run failure rolls the composed step back to the
    latest checkpoint (err buffers included) and completes."""
    fresh_state, py_step, s_shard, stream = _multipod_setup("gather")
    injector = fault.FaultInjector(fail_at={5})
    final, hist = fault.run_training(
        py_step, fresh_state(), stream.batch_at, num_steps=8,
        ckpt_dir=str(tmp_path), ckpt_every=3, log_every=0,
        fault_hook=injector, restore_shardings=s_shard,
    )
    assert injector.failures == 1
    assert int(final["step"]) == 8
    assert hist[-1]["step"] == 7


@eight_devices
@pytest.mark.slow
def test_multipod_loss_decreases_all_modes():
    """gather / two_stage / uncompressed all train the reduced config:
    compression does not break convergence on the composed path."""
    for scheme, compress in (("gather", True), ("two_stage", True),
                             ("gather", False)):
        fresh_state, py_step, _, stream = _multipod_setup(
            scheme, compress=compress
        )
        state = fresh_state()
        losses = []
        for i in range(24):
            state, m = py_step(state, stream.batch_at(i))
            losses.append(float(m["loss"]))
        # tiny (8, 16) batches make single-step losses ±0.2 noisy, so
        # compare 6-step window means (the trend, which is the claim)
        # rather than two individual samples
        first = sum(losses[:6]) / 6
        last = sum(losses[-6:]) / 6
        assert last < first - 0.05, (scheme, compress, losses)


def test_multipod_requires_pod_axis():
    mesh = jax.make_mesh(
        (1, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
    cfg = configs.reduced("qwen3_8b")
    with pytest.raises(ValueError, match="pod"):
        trainer.make_multipod_train_step(
            lambda p, b: (0.0, {}), optim.adamw(1e-3), cfg, mesh, {}
        )


def test_init_dp_err_shapes_and_validation():
    mesh = _pod_mesh(1)
    params = {"w": jnp.zeros((5, 3)), "b": jnp.zeros((7,))}
    with pytest.raises(ValueError, match="scheme"):
        trainer.init_dp_err(params, mesh, scheme="bogus")
    assert trainer.init_dp_err(params, mesh, compress=False) == {}
    err = trainer.init_dp_err(params, mesh, scheme="two_stage")
    assert err["s1"]["w"].shape == (1, 5, 3)
    assert err["s2"]["w"].shape == (1, C.two_stage_shard_len(15, 1))
    assert err["s2"]["b"].shape == (1, 7)
