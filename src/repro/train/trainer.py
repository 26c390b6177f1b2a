"""Trainer: train_step construction (+ sharded variant for the mesh).

`make_train_step(loss_fn, optimizer, ...)` returns a pure
(state, batch) -> (state, metrics) function with:
  * microbatch gradient accumulation (scan) when n_micro > 1,
  * global-norm clipping,
  * AdamW/optimizer update with schedule evaluated at state["step"].

`make_sharded_train_step(model, optimizer, mesh)` wraps it in jax.jit
with in/out shardings derived from `dist.sharding` — this exact jitted
function is what the dry-run lowers and what `launch/train.py` runs, so
the dry-run proves the production path, not a stand-in.

Multi-pod: `make_dp_step_compressed` is the pure shard_map DP step over
a pod axis (quantized gradient reduction via `dist.compression`,
scheme-selectable), and `make_multipod_train_step` composes the in-pod
sharded pjit step with that pod-axis reduction for
`launch/train.py --multi-pod`. Both carry per-pod error-feedback
buffers in state["err"] (`init_dp_err`), sharded P("pod") so
checkpoints capture every pod's residual.

State is a plain dict pytree {"params", "opt", "step"[, "err"]} so
checkpointing and resharding stay structure-generic.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.dist import sharding as shd
from repro.dist.accumulate import accumulate_grads
from repro.optim import clip_by_global_norm
from repro.optim.optimizers import Optimizer, apply_updates

# Declared collective envelope for the train-step cells, asserted by
# the `repro.analysis` cell audit. Data-parallel grad psums, FSDP
# gather/scatter pairs, the compressed cross-pod exchange (all-to-all /
# permute chains, scheme-dependent) and the global-norm reduction all
# land within a few hundred collectives per compiled step on the pod
# meshes the dist benchmark runs; the audit's job is to catch the
# orders-of-magnitude SPMD blowup class (a per-parameter resharding
# emitting thousands), not to pin exact per-scheme counts — those live
# in tests/test_hlo_count.py.
_TRAIN_COMM_ENVELOPE = {
    "all-reduce": 512,
    "all-gather": 512,
    "reduce-scatter": 512,
    "collective-permute": 512,
    "all-to-all": 512,
}


def init_state(params: Any, optimizer: Optimizer) -> dict:
    return {
        "params": params,
        "opt": optimizer.init(params),
        "step": jnp.zeros((), jnp.int32),
    }


def make_train_step(
    loss_fn: Callable[[Any, Any], tuple[jax.Array, dict]],
    optimizer: Optimizer,
    *,
    clip_norm: float = 1.0,
    n_micro: int = 1,
) -> Callable[[dict, Any], tuple[dict, dict]]:
    def grad_fn(params, mb):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, mb
        )
        return grads, metrics

    def train_step(state: dict, batch: Any) -> tuple[dict, dict]:
        grads, metrics = accumulate_grads(
            grad_fn, state["params"], batch, n_micro
        )
        if clip_norm:
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
        else:
            from repro.optim import global_norm

            gnorm = global_norm(grads)
        updates, opt = optimizer.update(
            grads, state["opt"], state["params"], state["step"]
        )
        params = apply_updates(state["params"], updates)
        new_state = {
            "params": params,
            "opt": opt,
            "step": state["step"] + 1,
        }
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        return new_state, metrics

    return train_step


def state_specs(state_shapes: Any, cfg, mesh: Mesh) -> Any:
    """PartitionSpecs for a {"params","opt","step"} state pytree:
    opt moments mirror param specs (ZeRO-1); step is replicated."""
    p_specs = shd.param_specs(state_shapes["params"], cfg, mesh)
    # m/v (and sgd mu) mirror the params tree leaf-for-leaf
    o = state_shapes["opt"]
    o_specs = {}
    for k, sub in o.items():
        if sub is None:
            o_specs[k] = None
        else:
            o_specs[k] = shd.param_specs(sub, cfg, mesh)
    return {"params": p_specs, "opt": o_specs, "step": P()}


def make_sharded_train_step(
    loss_fn: Callable,
    optimizer: Optimizer,
    cfg,
    mesh: Mesh,
    state_shapes: Any,
    batch_shapes: Any,
    *,
    clip_norm: float = 1.0,
    n_micro: int = 1,
    donate: bool = True,
):
    """Returns (jitted_step, state_shardings, batch_shardings)."""
    step = make_train_step(
        loss_fn, optimizer, clip_norm=clip_norm, n_micro=n_micro
    )
    s_specs = state_specs(state_shapes, cfg, mesh)
    b_specs = shd.batch_specs(batch_shapes, cfg, mesh)
    s_shard = shd.named(s_specs, mesh)
    b_shard = shd.named(b_specs, mesh)
    jitted = obs.get().probe.track(
        "train.step",
        jax.jit(
            step,
            in_shardings=(s_shard, b_shard),
            out_shardings=(s_shard, None),
            donate_argnums=(0,) if donate else (),
        ),
        budget=_TRAIN_COMM_ENVELOPE,
        donate=(0,) if donate else (),
        sharded_outputs=True,
    )
    return jitted, s_shard, b_shard


# ---------------------------------------------------------------------------
# Manual-DP step with compressed cross-pod gradients (shard_map)
# ---------------------------------------------------------------------------

_SCHEMES = ("gather", "two_stage")


def init_dp_err(
    params: Any,
    mesh: Mesh,
    *,
    axis: str = "pod",
    scheme: str = "gather",
    compress: bool = True,
) -> dict:
    """Zero error-feedback buffers for the compressed-DP steps, shaped
    for checkpointing: every leaf carries a leading (n_pods,) dim and is
    sharded `P(axis)` in the step, so each pod's residuals round-trip
    through `train.checkpoint` faithfully (the gathered array holds ALL
    pods' buffers, not one pod's copy). Restoring on a different pod
    count would silently break the telescoping identity, so shape
    mismatch fails loudly in `checkpoint.restore`.

      gather:    {"s1": tree[(n, *leaf.shape)]}
      two_stage: {"s1": tree[(n, *leaf.shape)],
                  "s2": tree[(n, ceil(|leaf|/n))]}
      compress=False: {} (the uncompressed path is stateless)
    """
    from repro.dist import compression as C

    if not compress:
        return {}
    if scheme not in _SCHEMES:
        raise ValueError(f"scheme {scheme!r}: expected one of {_SCHEMES}")
    n = mesh.shape[axis]
    err = {
        "s1": jax.tree.map(
            lambda p: jnp.zeros((n,) + tuple(p.shape), jnp.float32), params
        )
    }
    if scheme == "two_stage":
        err["s2"] = jax.tree.map(
            lambda p: jnp.zeros(
                (n, C.two_stage_shard_len(math.prod(p.shape) or 1, n)),
                jnp.float32,
            ),
            params,
        )
    # Seat the buffers with the steady-state sharding the step emits
    # (leading pod dim split over `axis`): uncommitted zeros would make
    # the step's second call retrace — one silent extra compile of the
    # full train step that the per-cell recompile telemetry flags.
    return jax.device_put(err, NamedSharding(mesh, P(axis)))


def _reduce_grads(grads, err, axis, *, compress, scheme):
    """Scheme dispatch shared by the DP steps (called inside shard_map;
    err leaves arrive with their leading (1,)-sized pod-block dim)."""
    from repro.dist import compression as C

    if not compress:
        return C.uncompressed_psum_mean(grads, axis), err
    sq = lambda t: jax.tree.map(lambda x: x[0], t)  # noqa: E731
    ex = lambda t: jax.tree.map(lambda x: x[None], t)  # noqa: E731
    if scheme == "gather":
        mean, s1 = C.compressed_psum_mean(grads, sq(err["s1"]), axis)
        return mean, {"s1": ex(s1)}
    if scheme == "two_stage":
        mean, s1, s2 = C.two_stage_psum_mean(
            grads, sq(err["s1"]), sq(err["s2"]), axis
        )
        return mean, {"s1": ex(s1), "s2": ex(s2)}
    raise ValueError(f"scheme {scheme!r}: expected one of {_SCHEMES}")


def make_dp_step_compressed(
    loss_fn: Callable,
    optimizer: Optimizer,
    mesh: Mesh,
    *,
    axis: str = "pod",
    clip_norm: float = 1.0,
    compress: bool = True,
    scheme: str = "gather",
):
    """Data-parallel train step over `axis` with quantized
    error-feedback gradient reduction (dist.compression). Params
    replicated over `axis`; batch sharded. State is
    {"params", "opt", "step", "err"} with `err` from `init_dp_err` —
    per-pod buffers sharded P(axis), so checkpoints capture every pod's
    residual and a restart preserves the telescoping-losslessness
    invariant bitwise.

    `scheme` picks the wire layout: "gather" (full-leaf int8
    all-gather, (8/n)x egress) or "two_stage" (quantized reduce-scatter
    + all-gather, n-independent ~4x) — crossover guidance in
    `dist.compression`'s docstring. `compress=False` runs the
    finite-guarded f32 pmean baseline (stateless, err stays {}).

    This is the cross-pod communication mode for multi-pod training —
    in-pod axes still use pjit/XLA collectives inside `loss_fn`; for
    the launcher's composed in-pod-sharded variant see
    `make_multipod_train_step`.
    """
    if compress and scheme not in _SCHEMES:
        raise ValueError(f"scheme {scheme!r}: expected one of {_SCHEMES}")

    def local_step(state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state["params"], batch)
        grads, new_err = _reduce_grads(
            grads, state["err"], axis, compress=compress, scheme=scheme
        )
        if clip_norm:
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
        else:
            gnorm = jnp.zeros(())
        updates, opt = optimizer.update(
            grads, state["opt"], state["params"], state["step"]
        )
        params = apply_updates(state["params"], updates)
        new_state = {
            "params": params,
            "opt": opt,
            "step": state["step"] + 1,
            "err": new_err,
        }
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, axis), metrics)
        return new_state, metrics

    rep = P()  # replicated across the dp axis
    dp = P(axis)
    state_spec = {"params": rep, "opt": rep, "step": rep, "err": dp}
    return jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(state_spec, dp),
        out_specs=(state_spec, rep),
        check_vma=False,
    )


# ---------------------------------------------------------------------------
# Composed multi-pod step: in-pod pjit + cross-pod compressed shard_map
# ---------------------------------------------------------------------------


def make_multipod_train_step(
    loss_fn: Callable,
    optimizer: Optimizer,
    cfg,
    mesh: Mesh,
    state_shapes: Any,
    *,
    scheme: str = "gather",
    compress: bool = True,
    clip_norm: float = 1.0,
    n_micro: int = 1,
    donate: bool = True,
):
    """Compressed multi-pod data-parallel training over a
    ("pod", "data", "model") mesh: the in-pod axes stay a sharded pjit
    step (XLA bf16/f32 collectives over ICI), only the pod axis routes
    through `dist.compression`. Three stages per step:

      A. per-pod gradients — `vmap(value_and_grad(loss_fn))` over a
         leading pod dim under jit: batch sharded ("pod", "data"),
         params sharded by `dist.sharding.param_specs` (data/model,
         replicated over pod). No cross-pod collectives: the pod dim is
         a batched dim, grads come out P("pod")-sharded.
      B. cross-pod reduction — full-manual shard_map over the whole
         mesh running the selected `dist.compression` scheme along
         "pod" (the exact collectives `benchmarks/dist_compression.py`
         accounts). Grads enter replicated over the in-pod axes (the
         gather at stage-A's exit is in-pod ICI traffic), so the error
         buffers' shapes depend only on the pod count, never the in-pod
         layout — checkpoints stay portable across in-pod reshapes.
      C. optimizer update — pjit under the ZeRO-1 `state_specs`
         shardings (clip + update on the replicated mean grads).

    The pod axis cannot be partial-manual on this jax/XLA: gather-family
    collectives inside a manual subgroup with auto in-pod axes abort the
    SPMD partitioner (spmd_partitioner.cc:512 IsManualSubgroup check),
    which is why the reduction runs full-manual on pod-replicated
    blocks instead.

    `state_shapes` is `jax.eval_shape` of the full state INCLUDING
    "err" (`init_dp_err`). Returns (py_step, state_shardings):
    `py_step(state, batch) -> (state, metrics)` reshapes flat
    (B, ...) batch leaves to (n_pod, B/n_pod, ...) internally — B must
    divide by the pod count — and is what `fault.run_training` drives;
    `state_shardings` feeds checkpoint-restore placement.
    """
    if compress and scheme not in _SCHEMES:
        raise ValueError(f"scheme {scheme!r}: expected one of {_SCHEMES}")
    if "pod" not in mesh.axis_names:
        raise ValueError(
            f"make_multipod_train_step needs a 'pod' mesh axis, got "
            f"{mesh.axis_names} (launch.mesh.make_multipod_mesh)"
        )
    n_pod = mesh.shape["pod"]
    n_data = mesh.shape.get("data", 1)

    core_shapes = {k: state_shapes[k] for k in ("params", "opt", "step")}
    core_specs = state_specs(core_shapes, cfg, mesh)
    core_shard = shd.named(core_specs, mesh)
    p_shard = core_shard["params"]
    err_spec = jax.tree.map(lambda _: P("pod"), state_shapes["err"])
    err_shard = shd.named(err_spec, mesh)
    state_shardings = {**core_shard, "err": err_shard}

    # ---- stage A: per-pod grads (pjit, in-pod axes auto) ----
    def grad_one(p, b):
        def gf(pp, mb):
            (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(pp, mb)
            return g, m

        return accumulate_grads(gf, p, b, n_micro)

    def pod_batch_spec(leaf):
        b_local = leaf.shape[0] // n_pod
        d = "data" if n_data <= 1 or b_local % n_data == 0 else None
        return P("pod", d, *([None] * (len(leaf.shape) - 2)))

    def pod_batch_shard(batch):
        return jax.tree.map(
            lambda x: jax.sharding.NamedSharding(mesh, pod_batch_spec(x)),
            batch,
        )

    g_shard = jax.tree.map(
        lambda _: jax.sharding.NamedSharding(mesh, P("pod")),
        state_shapes["params"],
    )

    # ---- stage B: cross-pod compressed reduction (full-manual) ----
    def reduce_body(grads, err):
        grads = jax.tree.map(lambda x: x[0], grads)  # (1, *leaf) block
        mean, new_err = _reduce_grads(
            grads, err, "pod", compress=compress, scheme=scheme
        )
        return mean, new_err

    g_spec = jax.tree.map(lambda _: P("pod"), state_shapes["params"])
    mean_spec = jax.tree.map(lambda _: P(), state_shapes["params"])
    step_b = obs.get().probe.track(
        "train.multipod.step_b",
        jax.jit(
            jax.shard_map(
                reduce_body,
                mesh=mesh,
                in_specs=(g_spec, err_spec),
                out_specs=(mean_spec, err_spec),
                check_vma=False,
            ),
            in_shardings=(g_shard, err_shard),
            out_shardings=(shd.named(mean_spec, mesh), err_shard),
            donate_argnums=(1,) if donate else (),
        ),
        budget=_TRAIN_COMM_ENVELOPE,
        donate=(1,) if donate else (),
        sharded_outputs=True,
    )

    # ---- stage C: optimizer update (pjit, ZeRO-1 shardings) ----
    def update_core(core, grads):
        if clip_norm:
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
        else:
            from repro.optim import global_norm

            gnorm = global_norm(grads)
        updates, opt = optimizer.update(
            grads, core["opt"], core["params"], core["step"]
        )
        return {
            "params": apply_updates(core["params"], updates),
            "opt": opt,
            "step": core["step"] + 1,
        }, gnorm

    step_c = obs.get().probe.track(
        "train.multipod.step_c",
        jax.jit(
            update_core,
            in_shardings=(core_shard, shd.named(mean_spec, mesh)),
            out_shardings=(core_shard, None),
            donate_argnums=(0,) if donate else (),
        ),
        budget=_TRAIN_COMM_ENVELOPE,
        donate=(0,) if donate else (),
        sharded_outputs=True,
    )

    step_a = None  # compiled lazily: in_shardings depend on batch shapes

    def py_step(state: dict, batch: Any) -> tuple[dict, dict]:
        nonlocal step_a
        tel = obs.get()
        leading = jax.tree.leaves(batch)[0].shape[0]
        if leading % n_pod:
            raise ValueError(
                f"multi-pod batch {leading} not divisible by "
                f"{n_pod} pods"
            )
        pb = jax.tree.map(
            lambda x: x.reshape((n_pod, -1) + x.shape[1:]), batch
        )
        if step_a is None:
            step_a = tel.probe.track(
                "train.multipod.step_a",
                jax.jit(
                    jax.vmap(grad_one, in_axes=(None, 0)),
                    in_shardings=(p_shard, pod_batch_shard(pb)),
                    out_shardings=(g_shard, None),
                ),
                budget=_TRAIN_COMM_ENVELOPE,
                sharded_outputs=True,
            )
        with tel.span("train/grads", cat="train"):
            grads, metrics = tel.block(step_a(state["params"], pb))
        with tel.span("train/reduce", cat="train"):
            mean_g, new_err = tel.block(step_b(grads, state["err"]))
        core = {k: state[k] for k in ("params", "opt", "step")}
        with tel.span("train/update", cat="train"):
            new_core, gnorm = step_c(core, mean_g)
        metrics = {k: jnp.mean(v) for k, v in metrics.items()}
        metrics["grad_norm"] = gnorm
        return {**new_core, "err": new_err}, metrics

    return py_step, state_shardings
