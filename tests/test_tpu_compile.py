"""Compiles for a described TPU v5e chip: what Mosaic and XLA:TPU refuse.

Nothing here runs; every test lowers and compiles for one chip of a
`v5e:2x2` topology described through libtpu, with Pallas interpret mode
off (passed in the test). That catches what interpret-mode kernel tests
cannot: block shapes not aligned to the (8, 128) tiling, casts Mosaic
has no lowering for, strided in-VMEM slices, and programs that do not
fit the chip's 16 GB. The topology is described inside a module fixture
(never at import), so every test skips where libtpu cannot describe it.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.configs import va_cnn
from repro.core import compiler, vadetect
from repro.kernels import ops
from repro.models import api

VA_SPARSE_LAYERS = [
    i for i, m in enumerate(vadetect.layer_shapes(va_cnn.CONFIG))
    if m["sparse"]
]
VA_BUCKETS = (8, 256)  # smallest and largest fleet bucket
HBM_BYTES = 16e9  # one v5e chip

# the served LM shape: qwen3-8b published widths, depth cut to 6 layers
LM_SLOTS, LM_PROMPT, LM_MAX_SEQ, LM_PAGE = 8, 128, 512, 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "can't"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def va_program():
    params = vadetect.init(jax.random.PRNGKey(0), va_cnn.CONFIG)
    return compiler.compile_model(params, va_cnn.CONFIG)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args) -> str:
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, "no Mosaic kernel in the program"
    return hlo


# -- kernels ----------------------------------------------------------------


@pytest.mark.parametrize("bucket", VA_BUCKETS)
@pytest.mark.parametrize("layer", VA_SPARSE_LAYERS)
def test_nm_spmm_va_layer(one_chip, va_program, layer, bucket):
    """The kernel behind the fleet's `kernel` path, at the im2col shape
    `compiler.execute` feeds it for every sparse VA layer."""
    m = va_program.layer_meta[layer]
    lay = va_program.layers[m["name"]]
    kk, n = lay.values_q.shape
    _compile_kernel(
        lambda x, v, s, sc: ops.nm_spmm(
            x, v, s, sc, group_size=lay.group_size, keep=lay.keep,
            interpret=False,
        ),
        _spec((bucket, m["t_out"], lay.k_dense), jnp.float32, one_chip),
        _spec((kk, n), lay.values_q.dtype, one_chip),
        _spec((kk, n), lay.select.dtype, one_chip),
        _spec((1, n), jnp.float32, one_chip),
    )


def test_nm_spmm_qwen3_8b_width(one_chip):
    """16:8 sparse qwen3-8b up-projection: K tiles of 256 (16 groups)."""
    k, n = 4096, 12288
    _compile_kernel(
        lambda x, v, s, sc: ops.nm_spmm(
            x, v, s, sc, group_size=16, keep=8, interpret=False,
        ),
        _spec((8, k), jnp.float32, one_chip),
        _spec((k // 2, n), jnp.int8, one_chip),
        _spec((k // 2, n), jnp.uint8, one_chip),
        _spec((1, n), jnp.float32, one_chip),
    )


# (name, M, K, N, bits): VA layer 0's kept rows (16:8 of the
# group-padded K = 32) at the largest bucket, and qwen3-8b's
# up-projection (d_model 4096 -> d_ff 12288) on a decode batch of 8
PACKED_CASES = [
    ("va_layer0", 256 * 256, 16, 16, 8),
    ("qwen3_8b_up_w8", 8, 4096, 12288, 8),
    ("qwen3_8b_up_w4", 8, 4096, 12288, 4),
]


@pytest.mark.parametrize("kernel", ["bitserial_matmul", "quant_matmul"])
@pytest.mark.parametrize("name,m,k,n,bits", PACKED_CASES,
                         ids=[c[0] for c in PACKED_CASES])
def test_packed_matmul(one_chip, kernel, name, m, k, n, bits):
    fn = getattr(ops, kernel)
    _compile_kernel(
        lambda x, p, sc: fn(x, p, sc, bits=bits, interpret=False),
        _spec((m, k), jnp.float32, one_chip),
        _spec((k * bits // 8, n), jnp.uint8, one_chip),
        _spec((1, n), jnp.float32, one_chip),
    )


@pytest.mark.parametrize("layer", VA_SPARSE_LAYERS)
def test_sparse_conv1d_va_layer(one_chip, va_program, layer):
    """Every sparse VA layer as one fused conv, strides 2 (layer 0
    among them) and 1, at the largest bucket."""
    m = va_program.layer_meta[layer]
    lay = va_program.layers[m["name"]]
    kk, n = lay.values_q.shape
    _compile_kernel(
        lambda x, v, s, sc: ops.sparse_conv1d(
            x, v, s, sc, ksize=m["ksize"], stride=m["stride"],
            group_size=lay.group_size, keep=lay.keep, interpret=False,
        ),
        _spec((max(VA_BUCKETS), m["t_in"], m["c_in"]), jnp.float32,
              one_chip),
        _spec((kk, n), lay.values_q.dtype, one_chip),
        _spec((kk, n), lay.select.dtype, one_chip),
        _spec((1, n), jnp.float32, one_chip),
    )


# -- the served LM steps ----------------------------------------------------


@pytest.fixture(scope="module")
def lm(one_chip):
    cfg = dataclasses.replace(configs.get("qwen3_8b"), n_layers=6)
    model = api.build_model(cfg, tp=1, max_seq=LM_MAX_SEQ)
    place = lambda tree: jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip), tree
    )
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    return model, params, place


def _fits(compiled) -> float:
    ma = compiled.memory_analysis()
    total = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert total < HBM_BYTES, f"{total / 1e9:.2f} GB does not fit"
    return total


def test_lm_decode_step_fits_one_chip(one_chip, lm):
    """The paged pool decode step the engine runs every tick: 8 slots,
    pages of 16, the default page pool (8 x 32 + 1 scratch pages)."""
    model, params, place = lm
    span = LM_MAX_SEQ // LM_PAGE
    n_pages = LM_SLOTS * span + 1
    cache = place(jax.eval_shape(
        lambda: model.init_cache_paged(LM_SLOTS, n_pages, LM_PAGE)
    ))
    vec = _spec((LM_SLOTS,), jnp.int32, one_chip)
    tbl = _spec((LM_SLOTS, span), jnp.int32, one_chip)
    compiled = jax.jit(
        lambda p, c, t, pos, tb: model.decode_step_paged(
            p, c, t, pos, tb, LM_PAGE
        )
    ).lower(params, cache, vec, vec, tbl).compile()
    _fits(compiled)


def test_lm_prefill_fits_one_chip(one_chip, lm):
    """The widest admission prefill: all 8 slots' 128-token prompts."""
    model, params, _ = lm
    toks = _spec((LM_SLOTS, LM_PROMPT), jnp.int32, one_chip)
    compiled = jax.jit(model.prefill).lower(params, toks).compile()
    _fits(compiled)
