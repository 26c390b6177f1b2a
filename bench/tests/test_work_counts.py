"""Work counts kept with each configuration."""

from __future__ import annotations

import os

import jax
import pytest

from bench import run as R

CONFIGS = os.path.join(R.BENCH, "configs")


@pytest.fixture(scope="module")
def va():
    return R.load_module(os.path.join(CONFIGS, "va_cnn.py"), "wc_va")


@pytest.fixture(scope="module")
def qwen():
    return R.load_module(os.path.join(CONFIGS, "qwen3_8b.py"), "wc_qwen")


def test_va_ops_are_twice_the_compiled_nonzero_macs(va):
    """2 x (kept weights x output positions) of the program the repo's
    compiler emits, for every layer."""
    from repro.core import compiler
    from repro.core.spe import SPEConfig
    from repro.core.vadetect import VAConfig

    cfg = va.load_config()
    vcfg = VAConfig(layers=tuple(tuple(x) for x in cfg["layers"]),
                    spe=SPEConfig(bits=8, group_size=16, keep=8))
    program = compiler.compile_model(
        va.make_params(jax.random.PRNGKey(0), cfg), vcfg)
    macs = sum(program.layers[m["name"]].values_q.size * m["t_out"]
               for m in program.layer_meta)
    assert va.classify_flops(cfg, 1) == 2 * macs
    assert va.classify_flops(cfg, 256) == 256 * 2 * macs
    assert va.macs_per_segment(cfg) == 1_240_064


def test_qwen_bytes_are_reckoned_at_the_compute_dtype(qwen):
    cfg = qwen.load_config()
    n = (cfg["num_hidden_layers"] * qwen.layer_params(cfg)
         + qwen.head_params(cfg))
    assert cfg["torch_dtype"] == "bfloat16"
    assert qwen.weight_bytes(cfg) == 2 * n
    assert qwen.weight_bytes(dict(cfg, torch_dtype="float32")) == 4 * n
    # a qwen3-8b layer holds ~193 M matmul weights
    assert qwen.layer_params(cfg) == 192_937_984
    f, b = qwen.decode_work(cfg, [300] * 16)
    assert b == pytest.approx(2 * n + 16 * 300
                              * qwen.kv_bytes_per_position(cfg)
                              + 16 * 4096 * 2 + 16 * 151936 * 4)
    assert f == pytest.approx(2 * 16 * n + 4 * 6 * 32 * 128 * 16 * 300)


def test_prefill_attention_is_causal(qwen):
    cfg = qwen.load_config()
    f1, _ = qwen.prefill_work(cfg, 1, 2048)
    f2, _ = qwen.prefill_work(cfg, 2, 2048)
    attn = 4 * 6 * 32 * 128 * 2048 * 2049 / 2
    assert f2 == pytest.approx(2 * f1)
    assert f1 == pytest.approx(2 * 2048 * 6 * qwen.layer_params(cfg) + attn
                               + 2 * qwen.head_params(cfg))
