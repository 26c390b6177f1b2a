"""Sharding/compression on a real >1-device mesh.

These need at least 2 devices: `scripts/ci.sh` forces 8 host CPU
devices (`--xla_force_host_platform_device_count=8`) so they run in CI;
on a plain single-device host they skip.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.dist import compression as C
from repro.dist import sharding as shd

multidevice = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >=2 devices (scripts/ci.sh forces 8 host devices)",
)


def _mesh(shape, names):
    n = math.prod(shape)
    return jax.make_mesh(
        shape, names, devices=jax.devices()[:n],
        axis_types=(jax.sharding.AxisType.Auto,) * len(names),
    )


@multidevice
def test_param_specs_divisibility_fallback_on_real_axis():
    """Odd dims on a true 2-way model axis: the non-dividing axis is
    dropped, the dividing one kept."""
    mesh = _mesh((1, 2), ("data", "model"))
    cfg = configs.reduced("qwen3_8b")
    assert shd._dim_ok(14, "model", mesh)
    assert not shd._dim_ok(7, "model", mesh)
    # spec_for_path: out_features 7 not divisible by 2 -> model dropped
    assert shd.spec_for_path(
        "blocks/pos0/mix/wq/w", (8, 7), cfg, mesh
    ) == P("data", None)
    assert shd.spec_for_path(
        "blocks/pos0/mix/wq/w", (8, 14), cfg, mesh
    ) == P("data", "model")
    # same guard through the tree-walking entry point
    shapes = {
        "blocks": {"pos0": {"mix": {"wq": {
            "w": jax.ShapeDtypeStruct((2, 8, 7), jnp.float32)
        }}}},
        "embed": {"w": jax.ShapeDtypeStruct((9, 8), jnp.float32)},
    }
    specs = shd.param_specs(shapes, cfg, mesh)
    assert specs["blocks"]["pos0"]["mix"]["wq"]["w"] == P(
        None, "data", None
    )
    # vocab 9 not divisible by model=2 -> embed row axis dropped
    assert specs["embed"]["w"] == P(None, "data")


@multidevice
def test_batch_specs_guard_on_real_axis():
    mesh = _mesh((2, 1), ("data", "model"))
    cfg = configs.reduced("qwen3_8b")
    tree = {
        "tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32),
        "odd": jax.ShapeDtypeStruct((3,), jnp.int32),
    }
    specs = shd.batch_specs(tree, cfg, mesh)
    assert specs["tokens"] == P(("data",), None)
    assert specs["odd"] == P(None)  # 3 not divisible by 2 -> replicated


@multidevice
def test_constrain_shards_across_devices():
    mesh = _mesh((2, 1), ("data", "model"))
    cfg = configs.reduced("qwen3_8b")
    with mesh, shd.activation_context(cfg, mesh):
        out = jax.jit(
            lambda x: shd.constrain(x + 1, "dp", None)
        )(jnp.zeros((4, 8)))
    np.testing.assert_allclose(out, 1.0)
    assert out.sharding.is_equivalent_to(
        NamedSharding(mesh, P(("data",), None)), out.ndim
    )


@multidevice
def test_compressed_psum_mean_matches_uncompressed():
    """int8+error-feedback mean across real devices stays within one
    quantization step of the f32 pmean, and mean + mean-of-residuals
    recovers it exactly (telescoping)."""
    n = jax.device_count()
    mesh = _mesh((n,), ("pod",))
    k = 256
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (n * k,))}
    e = {"w": jnp.zeros((n * k,))}

    comp = jax.shard_map(
        lambda gg, ee: C.compressed_psum_mean(gg, ee, "pod"),
        mesh=mesh, in_specs=(P("pod"), P("pod")),
        out_specs=(P(), P("pod")), check_vma=False,
    )
    unc = jax.shard_map(
        lambda gg: C.uncompressed_psum_mean(gg, "pod"),
        mesh=mesh, in_specs=(P("pod"),), out_specs=P(),
        check_vma=False,
    )
    mean_c, err = comp(g, e)
    mean_u = unc(g)

    amax = float(jnp.abs(g["w"]).max())
    np.testing.assert_allclose(
        np.asarray(mean_c["w"]), np.asarray(mean_u["w"]),
        atol=amax / 127.0,
    )
    residual_mean = np.asarray(err["w"]).reshape(n, k).mean(axis=0)
    np.testing.assert_allclose(
        np.asarray(mean_c["w"]) + residual_mean,
        np.asarray(mean_u["w"]), rtol=1e-5, atol=1e-6,
    )
