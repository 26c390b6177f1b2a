"""Plain float32 reference and work counts for `va_cnn` (arXiv 2410.17395).

Imports nothing of the program. The weights come from `make_params`, the
benchmark's own generator, in the layout the program's compiler reads
({"conv<i>": {"w": (ksize, c_in, c_out), "b": (c_out,)}}); the program
compiles them with its own pruning and quantization, and this module
prunes and quantizes them again the way the paper states:

- the contraction of a layer is the flattened (ksize * c_in) window,
  zero-padded to a whole number of groups of 16;
- every group keeps its 8 entries of largest magnitude, per output
  channel (the first layer and the 1x1 head excepted: the head is dense);
- kept weights take 8 bits, one symmetric scale per output channel
  (max |w| maps to 127, round half to even);
- convolutions use XLA's SAME padding, ReLU after every layer but the
  last, and the logits are the mean over positions;
- a diagnosis is the majority of 6 consecutive segment predictions of
  one patient, ties going to VA.

Everything runs at `jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_config() -> dict:
    with open(os.path.join(HERE, "va_cnn.json")) as f:
        return json.load(f)


def layer_table(cfg: dict) -> list[dict]:
    """Per layer: c_in, c_out, ksize, stride, t_in, t_out, sparse, the
    padded contraction K and the weights kept per output channel."""
    out = []
    t, c_in = cfg["record_len"], cfg["input_channels_padded"]
    n = len(cfg["layers"])
    g, keep = cfg["group_size"], cfg["keep"]
    for i, (c_out, ks, stride) in enumerate(cfg["layers"]):
        t_out = (t - 1) // stride + 1
        sparse = i < n - 1
        k = ks * c_in
        k_pad = k + (-k) % g if sparse else k
        out.append(dict(c_in=c_in, c_out=c_out, ksize=ks, stride=stride,
                        t_in=t, t_out=t_out, sparse=sparse, k=k,
                        k_pad=k_pad,
                        kept=(k_pad // g) * keep if sparse else k))
        t, c_in = t_out, c_out
    return out


def make_params(key: jax.Array, cfg: dict) -> dict:
    """Random weights from `key`, in one jitted call on the device."""
    table = layer_table(cfg)

    def make(key):
        params = {}
        for i, m in enumerate(table):
            kw, kb = jax.random.split(jax.random.fold_in(key, i))
            w = jax.random.normal(kw, (m["ksize"], m["c_in"], m["c_out"]),
                                  jnp.float32)
            params[f"conv{i}"] = {
                "w": w * np.float32((2.0 / (m["ksize"] * m["c_in"])) ** 0.5),
                "b": 0.05 * jax.random.normal(kb, (m["c_out"],), jnp.float32),
            }
        return params

    return jax.jit(make)(key)


def center_head(params: dict, cfg: dict, signals) -> dict:
    """Shift the head's biases so that, on `signals` (a sample of the
    cell's traffic), the reference puts half the segments in each class.
    Random weights otherwise favour one class for nearly every input,
    and a classifier that always answers the same hides faults."""
    last = f"conv{len(cfg['layers']) - 1}"
    logits = reference_logits(reference_weights(params, cfg), signals, cfg)
    shift = float(np.median(logits[:, 1] - logits[:, 0]))
    out = dict(params)
    b = np.asarray(params[last]["b"], np.float32).copy()
    b[0] += shift / 2
    b[1] -= shift / 2
    out[last] = {"w": params[last]["w"], "b": jnp.asarray(b)}
    return out


def quantize_layer(w: np.ndarray, m: dict, cfg: dict,
                   bits: int) -> np.ndarray:
    """(ksize, c_in, c_out) float weights -> the dequantized weights the
    configuration states: 16:8 pruned (sparse layers) and `bits`-bit
    with one scale per output channel."""
    w2 = np.asarray(w, np.float32).reshape(m["k"], m["c_out"])
    if m["sparse"]:
        g, keep = cfg["group_size"], cfg["keep"]
        wp = np.pad(w2, ((0, m["k_pad"] - m["k"]), (0, 0)))
        grp = wp.reshape(-1, g, m["c_out"])
        order = np.argsort(-np.abs(grp), axis=1, kind="stable")
        mask = np.zeros_like(grp, bool)
        np.put_along_axis(mask, order[:, :keep], True, axis=1)
        w2 = np.where(mask, grp, 0).reshape(-1, m["c_out"])[: m["k"]]
    qmax = 2 ** (bits - 1) - 1
    amax = np.maximum(np.abs(w2).max(axis=0, keepdims=True),
                      np.finfo(np.float32).tiny)
    scale = (amax / qmax).astype(np.float32)
    q = np.clip(np.round(w2 / scale), -qmax, qmax)
    return (q * scale).astype(np.float32).reshape(w.shape)


def reference_weights(params: dict, cfg: dict, bits: int = None) -> list:
    """Host float32 (w, b) per layer at `bits` (the configuration's
    `weight_bits` unless given: the control passes a lower width)."""
    bits = cfg["weight_bits"] if bits is None else bits
    out = []
    for i, m in enumerate(layer_table(cfg)):
        p = params[f"conv{i}"]
        out.append((quantize_layer(np.asarray(p["w"]), m, cfg, bits),
                    np.asarray(p["b"], np.float32)))
    return out


def _forward(weights, x, table):
    h = x
    n = len(table)
    for i, ((w, b), m) in enumerate(zip(weights, table)):
        pad = max((m["t_out"] - 1) * m["stride"] + m["ksize"] - m["t_in"],
                  0)
        h = jax.lax.conv_general_dilated(
            h, w, window_strides=(m["stride"],),
            padding=[(pad // 2, pad - pad // 2)],
            dimension_numbers=("NWC", "WIO", "NWC"),
        ) + b
        if i < n - 1:
            h = jax.nn.relu(h)
    return jnp.mean(h, axis=1)


def reference_logits(weights: list, signals, cfg: dict,
                     dtype=jnp.float32) -> np.ndarray:
    """(B, 512) signals -> (B, 2) logits. `dtype` is the arithmetic
    (float32 at HIGHEST for the reference)."""
    table = layer_table(cfg)
    x = jnp.asarray(signals, jnp.float32)[..., None]
    x = jnp.pad(x, ((0, 0), (0, 0),
                    (0, cfg["input_channels_padded"] - x.shape[-1])))
    ws = [(jnp.asarray(w, dtype), jnp.asarray(b, dtype))
          for w, b in weights]
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda ws, x: _forward(ws, x, table))(
            ws, x.astype(dtype))
    return np.asarray(out, np.float32)


def reference_votes(windows: np.ndarray, cfg: dict) -> np.ndarray:
    """(w, vote_segments) segment predictions, one vote window a row ->
    (w,) diagnoses."""
    windows = np.asarray(windows).reshape(-1, cfg["vote_segments"])
    return (2 * windows.sum(axis=1) >= cfg["vote_segments"]).astype(np.int64)


# -- work counts ------------------------------------------------------------


def macs_per_segment(cfg: dict) -> int:
    """Nonzero multiply-accumulates of one segment through the 16:8
    model: every kept weight, at every output position."""
    return sum(m["t_out"] * m["c_out"] * m["kept"]
               for m in layer_table(cfg))


def classify_flops(cfg: dict, rows: int) -> float:
    return 2.0 * macs_per_segment(cfg) * rows


def weight_bytes(cfg: dict) -> float:
    """The weights at their compiled width: values at `weight_bits`, a
    log2(group_size)-bit select per kept value of a sparse layer, and a
    float32 scale and bias per output channel."""
    sel_bits = max(1, (cfg["group_size"] - 1).bit_length())
    total = 0.0
    for m in layer_table(cfg):
        n_vals = m["kept"] * m["c_out"]
        total += n_vals * cfg["weight_bits"] / 8
        if m["sparse"]:
            total += n_vals * sel_bits / 8
        total += 2 * 4 * m["c_out"]
    return total


def classify_bytes(cfg: dict, rows: int) -> float:
    """Input signals (float32), weights, and the (rows, 2) float32 logits."""
    return rows * cfg["record_len"] * 4 + weight_bytes(cfg) + rows * 2 * 4
