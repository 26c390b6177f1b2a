"""Sharding rules, activation constraints, gradient compression."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.dist import compression as C
from repro.dist import sharding as shd
from repro.models import api


def _mesh22():
    # 1 real device: a (1,1) mesh exercises the rule plumbing
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def test_param_specs_rules():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = configs.reduced("qwen3_8b")
    model = api.build_model(cfg, tp=1, max_seq=8)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = shd.param_specs(shapes, cfg, mesh)
    blk = specs["blocks"]["pos0"]
    # stacked block params carry a leading (n_groups,) None dim
    assert blk["mix"]["wq"]["w"] == P(None, "data", "model")
    assert blk["mix"]["wo"]["w"] == P(None, "model", "data")
    assert blk["ffn"]["w_down"]["w"] == P(None, "model", "data")
    assert specs["embed"]["w"] == P("model", "data")
    assert specs["lm_head"]["w"] == P("data", "model")
    assert blk["ln1"]["scale"] == P()


def test_divisibility_guard_drops_axis():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    # shape 7 not divisible by fake axis size -> but axis size 1 divides
    # everything; test the guard logic directly instead:
    assert shd._dim_ok(8, "model", mesh)
    # construct a pretend mesh dict via spec_for_path on odd dims
    cfg = configs.reduced("qwen3_8b")
    spec = shd.spec_for_path("blocks/pos0/mix/wq/w", (7, 13), cfg, mesh)
    assert spec == P("data", "model")  # axis size 1 divides


def test_pure_dp_profile_replicates_params():
    mesh = _mesh22()
    cfg = configs.reduced("whisper_tiny")  # use_tp=False, fsdp=False
    spec = shd.spec_for_path("dec_blocks/self_attn/wq/w", (48, 48), cfg,
                             mesh)
    assert spec == P(None, None)
    assert shd.data_axes(cfg, mesh) == ("data", "model")


def test_batch_specs_guard():
    mesh = _mesh22()
    cfg = configs.reduced("qwen3_8b")
    tree = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32),
            "pos": jax.ShapeDtypeStruct((1,), jnp.int32)}
    specs = shd.batch_specs(tree, cfg, mesh)
    assert specs["tokens"] == P(("data",), None)
    # batch=1 divisible by axis 1 -> still sharded on the (1,1) mesh
    assert specs["pos"] == P(("data",))


def test_constrain_noop_outside_context():
    x = jnp.ones((4, 4))
    y = shd.constrain(x, "dp", None)
    assert y is x


def test_constrain_applies_in_context():
    mesh = _mesh22()
    cfg = configs.reduced("qwen3_8b")
    with mesh, shd.activation_context(cfg, mesh):
        out = jax.jit(
            lambda x: shd.constrain(x * 2, "dp", None, "tp")
        )(jnp.ones((2, 4, 8)))
    np.testing.assert_allclose(out, 2.0)


# --- gradient compression ---------------------------------------------------


def test_quantize_dequantize_error_bound():
    g = jax.random.normal(jax.random.PRNGKey(0), (256,))
    q, s = C.quantize_leaf(g)
    err = jnp.abs(C.dequantize_leaf(q, s) - g)
    assert float(err.max()) <= float(s) / 2 + 1e-7


def test_error_feedback_is_lossless_over_time():
    """sum of transmitted dequantized grads + final residual == sum of
    true grads (telescoping error feedback identity)."""
    key = jax.random.PRNGKey(1)
    grads = [jax.random.normal(jax.random.fold_in(key, i), (64,))
             for i in range(20)]
    err = jnp.zeros((64,))
    sent = jnp.zeros((64,))
    for g in grads:
        q, s, err = C.compress_residual(g, err)
        sent = sent + C.dequantize_leaf(q, s)
    total = sum(grads)
    np.testing.assert_allclose(sent + err, total, rtol=1e-4, atol=1e-4)


def test_compressed_sgd_converges():
    """Quadratic descent with int8+error-feedback gradients reaches the
    optimum — compression does not bias convergence."""
    w = jnp.array([3.0, -2.0, 1.5, -0.5] * 16)
    err = jnp.zeros_like(w)
    for _ in range(300):
        g = 2 * w  # grad of ||w||^2
        q, s, err = C.compress_residual(g, err)
        w = w - 0.05 * C.dequantize_leaf(q, s)
    assert float(jnp.abs(w).max()) < 1e-2


def test_compressed_psum_mean_single_device():
    """Under a 1-device shard_map the compressed mean == plain mean."""
    mesh = jax.make_mesh((1,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    g = {"w": jnp.arange(8.0)}
    e = {"w": jnp.zeros(8)}
    f = jax.shard_map(
        lambda gg, ee: C.compressed_psum_mean(gg, ee, "pod"),
        mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False,
    )
    mean, new_e = f(g, e)
    np.testing.assert_allclose(mean["w"] + new_e["w"], g["w"], rtol=1e-4,
                               atol=1e-4)


def test_two_stage_single_device_telescopes():
    """n=1 degenerates to double quantization of the same leaf; the
    output plus both residuals still reconstructs the input exactly."""
    mesh = jax.make_mesh((1,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    g = {"w": jax.random.normal(jax.random.PRNGKey(3), (37,))}  # odd size
    e1 = {"w": jnp.zeros(37)}
    e2 = {"w": jnp.zeros(C.two_stage_shard_len(37, 1))}
    f = jax.shard_map(
        lambda a, b, c: C.two_stage_psum_mean(a, b, c, "pod"),
        mesh=mesh, in_specs=(P(), P(), P()), out_specs=(P(), P(), P()),
        check_vma=False,
    )
    mean, n1, n2 = f(g, e1, e2)
    np.testing.assert_allclose(
        np.asarray(mean["w"] + n1["w"] + n2["w"][:37]),
        np.asarray(g["w"]), rtol=1e-4, atol=1e-5,
    )


def test_uncompressed_finite_guard():
    """`compress=False` shares failure semantics with the compressed
    path by default: non-finite entries are zeroed, not propagated;
    `finite_guard=False` is the documented raw-IEEE opt-out."""
    mesh = jax.make_mesh((1,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    g = {"w": jnp.array([1.0, jnp.inf, -jnp.inf, jnp.nan, 2.0])}

    def run(**kw):
        return jax.shard_map(
            lambda gg: C.uncompressed_psum_mean(gg, "pod", **kw),
            mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False,
        )(g)

    guarded = run()
    np.testing.assert_array_equal(
        np.asarray(guarded["w"]), [1.0, 0.0, 0.0, 0.0, 2.0]
    )
    raw = run(finite_guard=False)
    assert not bool(jnp.isfinite(raw["w"]).all())
