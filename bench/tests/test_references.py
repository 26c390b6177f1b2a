"""The plain float32 references against the program on the CPU, and
their controls (the reference one precision step below the
configuration) against the program's readings."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as R

CONFIGS = os.path.join(R.BENCH, "configs")


@pytest.fixture(scope="module")
def va():
    return R.load_module(os.path.join(CONFIGS, "va_cnn.py"), "ref_va")


@pytest.fixture(scope="module")
def qwen():
    return R.load_module(os.path.join(CONFIGS, "qwen3_8b.py"), "ref_qwen")


@pytest.fixture(scope="module")
def va_case(va):
    from repro.core import compiler
    from repro.core.spe import SPEConfig
    from repro.core.vadetect import VAConfig
    from repro.stream.sources import FleetSource, SourceConfig

    cfg = va.load_config()
    vcfg = VAConfig(layers=tuple(tuple(x) for x in cfg["layers"]),
                    spe=SPEConfig(bits=8, group_size=16, keep=8))
    x = FleetSource(SourceConfig(n_patients=64, seed=3)).signals(
        np.arange(256) % 64, np.arange(256) // 64)["signal"]
    params = va.center_head(va.make_params(jax.random.PRNGKey(4), cfg),
                            cfg, x)
    return cfg, vcfg, params, compiler.compile_model(params, vcfg), x


def test_va_reference_matches_the_twin_path(va, va_case):
    from repro.stream.runner import _twin_logits, twin_weights

    cfg, _, params, program, x = va_case
    ref = va.reference_logits(va.reference_weights(params, cfg), x, cfg)
    with jax.default_matmul_precision("highest"):
        twin = np.asarray(_twin_logits(twin_weights(program),
                                       program.layer_meta, x))
    np.testing.assert_allclose(twin, ref, atol=1e-5 * np.abs(ref).max())
    # the head is centred: both classes occur
    assert 0.2 < (ref.argmax(-1) == 1).mean() < 0.8


def test_va_control_fails_the_limit(va, va_case):
    """The reference with int4 weights in the program's place answers
    unlike the float32 reference on a larger share of segments than the
    limit the configuration's 8 bits are held to."""
    cfg, _, params, _, x = va_case
    limit = cfg["limits"]["va_wrong_share"]
    ref = va.reference_logits(va.reference_weights(params, cfg), x, cfg)
    low = va.reference_logits(va.reference_weights(params, cfg, bits=4),
                              x, cfg)
    assert np.mean(low.argmax(-1) != ref.argmax(-1)) > limit


def test_va_reference_vote(va):
    cfg = va.load_config()
    votes = va.reference_votes(np.array([[1, 1, 1, 0, 0, 0],
                                         [1, 1, 0, 0, 0, 0]]), cfg)
    assert votes.tolist() == [1, 0]


SMALL = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, num_hidden_layers=2,
             vocab_size=8192)


def _small_model(qwen, dtype="bfloat16"):
    from repro.configs.base import ArchConfig
    from repro.models import api

    cfg = dict(qwen.load_config(), **SMALL)
    arch = ArchConfig(name="small", family="dense", n_layers=2, d_model=128,
                      n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256,
                      vocab=8192, qk_norm=True, rope_theta=1e6, dtype=dtype)
    return cfg, api.build_model(arch, tp=1, max_seq=96)


def test_qwen_reference_matches_the_program_in_float32(qwen):
    cfg, model = _small_model(qwen, dtype="float32")
    key = jax.random.PRNGKey(1)
    params = qwen.make_params(key, jax.eval_shape(model.init, key))
    toks = np.asarray(jax.random.randint(key, (2, 24), 0, 8192))
    with jax.default_matmul_precision("highest"):
        last, _ = jax.jit(model.prefill)(params, jnp.asarray(toks))
    served = np.full(toks.shape, -1)
    served[:, -1] = np.asarray(jnp.argmax(last, -1))
    out = qwen.token_gaps(key, cfg, toks, served)
    assert np.all(out["served_gap"][:, -1] == 0.0)
    assert np.all(out["first_gap"][:, -1] == 0.0)


def test_qwen_controls_read_three_times_the_program(qwen):
    """Prefill then greedy decode through the program's cache, against
    the reference; the fp8 control reads at least three times the widest
    gap the program reads, seed for seed."""
    from repro.serve.engine import generate

    cfg, model = _small_model(qwen)
    prog, ctrl = [], []
    for seed in range(3):
        key = jax.random.PRNGKey(seed)
        params = qwen.make_params(key, jax.eval_shape(model.init, key))
        prompts = jax.random.randint(jax.random.PRNGKey(100 + seed),
                                     (4, 16), 0, 8192)
        out = np.asarray(generate(model, params, prompts, max_new=48))
        toks = np.concatenate([np.asarray(prompts), out[:, :-1]], 1)
        served = np.full(toks.shape, -1)
        served[:, 15:15 + 48] = out
        mask = served >= 0
        prog.append(qwen.token_gaps(key, cfg, toks, served)
                    ["served_gap"][mask].max())
        ctrl.append(qwen.token_gaps(key, cfg, toks, served, precision="fp8")
                    ["first_gap"][mask].max())
    assert min(ctrl) > 3 * max(prog), (prog, ctrl)
