"""Plain float32 reference forward and work counts for `qwen3_8b`
(hf:Qwen/Qwen3-8B, depth cut to `num_hidden_layers`).

Imports nothing of the program. The weights come from `make_params`, the
benchmark's own generator; it fills the parameter tree the program
declares (paths and shapes only) and can regenerate any one layer of it,
so the reference can run layer by layer after the program's state is
freed. The forward follows the published architecture:

- token embedding (untied from the LM head);
- per layer: RMSNorm (eps from the config) -> q, k, v projections (no
  bias) -> RMSNorm over each head of q and of k (qk-norm) -> rotary
  embedding (theta from the config, rotate-half pairing, positions
  0..S-1) -> causal grouped-query attention (query head h reads key/value
  head h // (heads / kv_heads), scores scaled by 1/sqrt(head_dim)) ->
  output projection, residual; RMSNorm -> SwiGLU MLP (down(silu(gate(x))
  * up(x))), residual;
- final RMSNorm, LM head, float32 logits.

The reference computes in float32 at `jax.default_matmul_precision(
"highest")`. The configuration computes in bfloat16; the control is the
reference one step below it, `precision="fp8"`: every matmul's weight is
rounded to float8_e4m3 with one scale per output channel and its input
with one scale per row, the rest as the reference.
"""

from __future__ import annotations

import json
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_config() -> dict:
    with open(os.path.join(HERE, "qwen3_8b.json")) as f:
        return json.load(f)


# -- weights ----------------------------------------------------------------


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def leaf_value(key: jax.Array, path: str, layer: int,
               shape: tuple) -> jax.Array:
    """Layer `layer`'s slice of the parameter at `path`, float32."""
    k = jax.random.fold_in(
        jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF),
        layer,
    )
    z = jax.random.normal(k, shape, jnp.float32)
    if path.endswith("scale"):  # RMSNorm gains
        return 1.0 + 0.1 * z
    if path.startswith("embed/"):
        return z
    return z * np.float32(shape[-2] ** -0.5)  # (fan_in, fan_out) weights


def is_stacked(path: str) -> bool:
    """Leaves under `blocks/` carry a leading layer axis."""
    return path.startswith("blocks/")


def make_params(key: jax.Array, shapes) -> dict:
    """Fill the program's parameter tree `shapes` (a pytree of
    ShapeDtypeStruct) from `key`, in one jitted call on the device."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        leaves = []
        for path, sds in flat:
            p = _path_str(path)
            if is_stacked(p):
                leaves.append(jnp.stack([
                    leaf_value(key, p, l, tuple(sds.shape[1:]))
                    for l in range(sds.shape[0])
                ]).astype(sds.dtype))
            else:
                leaves.append(
                    leaf_value(key, p, 0, tuple(sds.shape)).astype(sds.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make)(key)


# paths of the parameter tree the reference reads (the program's layout)
_LAYER = {
    "ln1": "blocks/pos0/ln1/scale",
    "ln2": "blocks/pos0/ln2/scale",
    "wq": "blocks/pos0/mix/wq/w",
    "wk": "blocks/pos0/mix/wk/w",
    "wv": "blocks/pos0/mix/wv/w",
    "wo": "blocks/pos0/mix/wo/w",
    "q_norm": "blocks/pos0/mix/q_norm/scale",
    "k_norm": "blocks/pos0/mix/k_norm/scale",
    "w_gate": "blocks/pos0/ffn/w_gate/w",
    "w_up": "blocks/pos0/ffn/w_up/w",
    "w_down": "blocks/pos0/ffn/w_down/w",
}


def _layer_shapes(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return {
        "ln1": (d,), "ln2": (d,), "wq": (d, h * hd), "wk": (d, kv * hd),
        "wv": (d, kv * hd), "wo": (h * hd, d), "q_norm": (hd,),
        "k_norm": (hd,), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
    }


def layer_weights(key, cfg: dict, layer: int) -> dict:
    shapes = _layer_shapes(cfg)
    return jax.jit(lambda key: {
        n: leaf_value(key, p, layer, shapes[n]) for n, p in _LAYER.items()
    })(key)


# -- forward ----------------------------------------------------------------


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _fp8(x, axis):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
    s = s / 448.0  # the largest float8_e4m3fn
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, precision):
    if precision == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)  # scales per row, per out channel
    return x @ w


def _rope(x, theta):
    # x (B, S, H, hd); rotate-half pairing, positions 0..S-1
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(w, x, cfg, precision):
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    b, s, _ = x.shape
    a = _rms(x, w["ln1"], eps)
    q = _mm(a, w["wq"], precision).reshape(b, s, h, hd)
    k = _mm(a, w["wk"], precision).reshape(b, s, kv, hd)
    v = _mm(a, w["wv"], precision).reshape(b, s, kv, hd)
    q = _rope(_rms(q, w["q_norm"], eps), theta)
    k = _rope(_rms(k, w["k_norm"], eps), theta)
    q = q.reshape(b, s, kv, h // kv, hd)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / np.float32(hd ** 0.5)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, -1), v)
    x = x + _mm(out.reshape(b, s, h * hd), w["wo"], precision)
    f = _rms(x, w["ln2"], eps)
    g = jax.nn.silu(_mm(f, w["w_gate"], precision))
    return x + _mm(g * _mm(f, w["w_up"], precision), w["w_down"], precision)


def token_gaps(key, cfg: dict, tokens: np.ndarray, served: np.ndarray,
               precision: str = "highest") -> dict:
    """Run the forward over `tokens` (B, S) and read, at every position
    with `served[b, t] >= 0`, the gap by which the served token's logit
    lies below the reference's best, and the gap of the token the
    `precision` run puts first (for the control). Returns host arrays
    {"served_gap", "first_gap"} over the marked positions."""
    tokens = jnp.asarray(tokens, jnp.int32)
    served = jnp.asarray(served, jnp.int32)
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hcfg = _hashable(cfg)
    rows = range(tokens.shape[0])
    with jax.default_matmul_precision("highest"):
        emb = jax.jit(lambda key: leaf_value(key, "embed/w", 0, (v, d)))(key)
        x = [emb[tokens[b: b + 1]] for b in rows]
        del emb
        xc = list(x)
        step = jax.jit(_layer, static_argnums=(2, 3))
        for layer in range(cfg["num_hidden_layers"]):
            w = layer_weights(key, cfg, layer)
            x = [step(w, h, hcfg, "highest") for h in x]
            if precision != "highest":
                xc = [step(w, h, hcfg, precision) for h in xc]
            del w
        head = jax.jit(_head, static_argnums=(3, 4))
        outs = [head(key, x[b], xc[b], hcfg, precision, served[b: b + 1])
                for b in rows]
    return {k: np.concatenate([np.asarray(o[k]) for o in outs])
            for k in outs[0]}


def _head(key, x, xc, cfg, precision, served):
    d, v, eps = cfg["hidden_size"], cfg["vocab_size"], cfg["rms_norm_eps"]
    w = leaf_value(key, "lm_head/w", 0, (d, v))
    g = leaf_value(key, "final_norm/scale", 0, (d,))
    logits = _rms(x, g, eps) @ w
    best = jnp.max(logits, -1)
    tok = jnp.maximum(served, 0)
    got = jnp.take_along_axis(logits, tok[..., None], -1)[..., 0]
    if precision == "highest":
        first = jnp.argmax(logits, -1)
    else:
        first = jnp.argmax(_mm(_rms(xc, g, eps), w, precision), -1)
    alt = jnp.take_along_axis(logits, first[..., None], -1)[..., 0]
    mask = served >= 0
    return {
        "served_gap": jnp.where(mask, best - got, -1.0),
        "first_gap": jnp.where(mask, best - alt, -1.0),
    }


class _hashable(dict):
    """A config dict usable as a static jit argument."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


# -- work counts ------------------------------------------------------------


def layer_params(cfg: dict) -> int:
    """Matmul weights of one layer (norm gains are negligible)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def compute_bytes(cfg: dict) -> int:
    """Bytes per element at the configuration's compute dtype."""
    return {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]]


def kv_bytes_per_position(cfg: dict) -> int:
    """Key and value bytes one position holds across the layers."""
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * compute_bytes(cfg))


def weight_bytes(cfg: dict) -> int:
    """Every weight one step must read once, at the compute dtype."""
    return ((cfg["num_hidden_layers"] * layer_params(cfg) + head_params(cfg))
            * compute_bytes(cfg))


def decode_work(cfg: dict, contexts) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over slots whose caches hold
    `contexts` positions each (the new token included)."""
    ctx = np.asarray(contexts, np.float64)
    n = len(ctx)
    L = cfg["num_hidden_layers"]
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    flops = (2.0 * n * (L * layer_params(cfg) + head_params(cfg))
             + 4.0 * L * h * hd * ctx.sum())
    nbytes = (weight_bytes(cfg) + kv_bytes_per_position(cfg) * ctx.sum()
              + n * cfg["hidden_size"] * compute_bytes(cfg)
              + n * cfg["vocab_size"] * 4)
    return flops, nbytes


def prefill_work(cfg: dict, n: int, s: int) -> tuple[float, float]:
    """(FLOPs, bytes) of a prefill of `n` prompts of `s` tokens that
    produces the last position's logits (causal attention)."""
    L = cfg["num_hidden_layers"]
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    tokens = float(n * s)
    flops = (2.0 * tokens * L * layer_params(cfg)
             + 4.0 * L * h * hd * n * s * (s + 1) / 2.0
             + 2.0 * n * head_params(cfg))
    nbytes = (weight_bytes(cfg) + kv_bytes_per_position(cfg) * tokens
              + tokens * cfg["hidden_size"] * compute_bytes(cfg)
              + n * cfg["vocab_size"] * 4)
    return flops, nbytes
