"""AOT step builders: (arch x shape x mesh) -> jitted-with-shardings step
function + abstract input ShapeDtypeStructs.

These are the functions the dry-run lowers and compiles for every assigned
cell, and the same builders the real train/serve launchers use — there is
exactly one definition of each step.

input_specs() follows the shannon/kernels pattern: weak-type-correct
ShapeDtypeStructs, shardable, zero device allocation.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import SHAPES, ModelConfig, ShapeConfig
from ..distributed import ctx as dist_ctx
from ..distributed.sharding import (
    batch_specs,
    cache_specs,
    dp_axes,
    dp_size,
    param_specs,
    to_named,
    zero1_specs,
)
from ..models.model import (
    decode_step,
    forward_train,
    init_caches,
    init_params,
    prefill,
)
from ..training.optimizer import OptConfig, adamw_init, adamw_update

PyTree = Any


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _filter_tree(tree: Dict, keys) -> Dict:
    return {k: v for k, v in tree.items() if k in keys}


def param_shapes(cfg: ModelConfig) -> PyTree:
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


def batch_shapes(cfg: ModelConfig, shape: ShapeConfig) -> Dict:
    """Abstract input batch for one shape cell."""
    b = shape.global_batch
    s = shape.seq_len if shape.kind != "decode" else 1
    out: Dict[str, Any] = {}
    if cfg.embed_input:
        out["inputs"] = _sds((b, s), jnp.int32)
    else:
        out["embeds"] = _sds((b, s, cfg.d_model), jnp.dtype(cfg.dtype))
    if shape.kind == "train":
        out["targets"] = _sds((b, s), jnp.int32)
    if cfg.family == "vlm":
        out["vision_states"] = _sds((b, cfg.n_image_tokens, cfg.d_model), jnp.dtype(cfg.dtype))
    return out


def cache_shapes(cfg: ModelConfig, shape: ShapeConfig) -> PyTree:
    return jax.eval_shape(
        lambda: init_caches(
            None, cfg, shape.global_batch, shape.seq_len, n_img=cfg.n_image_tokens
        )
    )


class _CtxJit:
    """jax.jit is lazy — tracing happens at .lower()/first call, which may
    be far from where the step was built. This wrapper re-enters the
    sharding context at trace time so dist_ctx.constrain() hints are live.

    Tracing also runs with x64 disabled: the store layer enables x64
    globally, under which layer-scan loop counters lower to s64 while the
    SPMD partitioner's shard-offset arithmetic stays s32 — the transposed
    scan's dynamic_update_slice then fails HLO verification with a mixed
    s64/s32 compare. Every tensor in the model/optimizer step is explicitly
    32-bit (or bf16), so tracing x64-off only pins index dtypes to s32,
    making both compare operands a common dtype."""

    def __init__(self, fn, mesh, rules):
        self._fn = fn
        self._mesh = mesh
        self._rules = rules

    def lower(self, *args, **kw):
        with dist_ctx.sharding_context(self._mesh, self._rules), jax.enable_x64(False):
            return self._fn.lower(*args, **kw)

    def __call__(self, *args, **kw):
        with dist_ctx.sharding_context(self._mesh, self._rules), jax.enable_x64(False):
            return self._fn(*args, **kw)


@dataclass
class BuiltStep:
    fn: Callable  # jitted with shardings (ctx-wrapped)
    abstract_args: Tuple  # ShapeDtypeStructs to .lower() with
    in_shardings: PyTree
    out_shardings: PyTree
    rules: Dict


def opt_state_specs(cfg, mesh, pspecs, pshapes, opt_cfg: OptConfig, zero1: bool):
    mv = zero1_specs(pspecs, pshapes, mesh) if zero1 else pspecs
    st = {"step": P(), "m": mv, "v": mv}
    if opt_cfg.compress_grads:
        st["err"] = mv
    return st


def build_train_step(
    cfg: ModelConfig,
    mesh: Mesh,
    shape: ShapeConfig,
    opt_cfg: Optional[OptConfig] = None,
    zero1: bool = True,
    remat: bool = True,
    loss_chunk: int = 512,
    seq_parallel: bool = False,
    accum_steps: int = 1,
) -> BuiltStep:
    """accum_steps > 1: gradient-accumulation microbatching — the global
    batch splits into accum_steps microbatches scanned sequentially with an
    f32 grad accumulator; activation footprints scale ~1/accum_steps at the
    cost of one accumulator tree (f32, model-sharded)."""
    opt_cfg = opt_cfg or OptConfig()
    pshapes = param_shapes(cfg)
    pspecs = param_specs(cfg, mesh)
    oshapes = jax.eval_shape(lambda: adamw_init(pshapes, opt_cfg))
    ospecs = opt_state_specs(cfg, mesh, pspecs, pshapes, opt_cfg, zero1)
    bshapes = batch_shapes(cfg, shape)
    bspecs = _filter_tree(batch_specs(cfg, mesh, shape.global_batch), bshapes.keys())
    rules = dist_ctx.default_rules(
        cfg, mesh, shape.global_batch, seq_parallel=seq_parallel, seq_len=shape.seq_len
    )
    assert shape.global_batch % accum_steps == 0

    def grad_fn(params, batch):
        def loss_fn(p):
            return forward_train(p, cfg, batch, remat=remat, loss_chunk=loss_chunk)

        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    def step(params, opt_state, batch):
        if accum_steps == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape(accum_steps, x.shape[0] // accum_steps, *x.shape[1:]),
                batch,
            )

            def body(acc, mb):
                g_acc, l_acc = acc
                # Keep each microbatch dp-sharded.
                mb = {k: dist_ctx.constrain("microbatch_" + ("3d" if v.ndim == 3 else "2d"), v)
                      for k, v in mb.items()}
                (loss, _), grads = grad_fn(params, mb)
                g_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), g_acc, grads
                )
                return (g_acc, l_acc + loss), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (grads, loss_sum), _ = jax.lax.scan(body, (g0, jnp.float32(0.0)), micro)
            grads = jax.tree_util.tree_map(lambda g: g / accum_steps, grads)
            loss = loss_sum / accum_steps
            metrics = {"loss": loss, "aux_loss": jnp.float32(0.0), "tokens": jnp.float32(0.0)}
        new_params, new_opt, opt_metrics = adamw_update(params, grads, opt_state, opt_cfg)
        metrics = {**metrics, **opt_metrics, "total_loss": loss}
        return new_params, new_opt, metrics

    in_sh = (to_named(pspecs, mesh), to_named(ospecs, mesh), to_named(bspecs, mesh))
    out_sh = (
        to_named(pspecs, mesh),
        to_named(ospecs, mesh),
        jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), {
            "loss": 0, "aux_loss": 0, "tokens": 0, "grad_norm": 0, "lr": 0, "total_loss": 0
        }),
    )
    fn = _CtxJit(jax.jit(step, in_shardings=in_sh, out_shardings=out_sh), mesh, rules)
    return BuiltStep(
        fn=fn,
        abstract_args=(pshapes, oshapes, bshapes),
        in_shardings=in_sh,
        out_shardings=out_sh,
        rules=rules,
    )


def build_prefill_step(cfg: ModelConfig, mesh: Mesh, shape: ShapeConfig) -> BuiltStep:
    pshapes = param_shapes(cfg)
    pspecs = param_specs(cfg, mesh)
    bshapes = batch_shapes(cfg, shape)
    bspecs = _filter_tree(batch_specs(cfg, mesh, shape.global_batch), bshapes.keys())
    cspecs = cache_specs(cfg, mesh, shape.global_batch)
    rules = dist_ctx.default_rules(cfg, mesh, shape.global_batch)
    dp = dp_axes(mesh)
    b_ax = dp if shape.global_batch % dp_size(mesh) == 0 else None
    vdiv = cfg.vocab_size % mesh.shape.get("model", 1) == 0

    def step(params, batch):
        logits, caches, last_pos = prefill(params, cfg, batch, cache_len=shape.seq_len)
        return logits, caches, last_pos

    in_sh = (to_named(pspecs, mesh), to_named(bspecs, mesh))
    out_sh = (
        NamedSharding(mesh, P(b_ax, "model" if vdiv else None)),
        to_named(cspecs, mesh),
        NamedSharding(mesh, P(b_ax)),
    )
    fn = _CtxJit(jax.jit(step, in_shardings=in_sh, out_shardings=out_sh), mesh, rules)
    return BuiltStep(fn, (pshapes, bshapes), in_sh, out_sh, rules)


def build_decode_step(cfg: ModelConfig, mesh: Mesh, shape: ShapeConfig) -> BuiltStep:
    pshapes = param_shapes(cfg)
    pspecs = param_specs(cfg, mesh)
    bshapes = batch_shapes(cfg, shape)
    bspecs = _filter_tree(batch_specs(cfg, mesh, shape.global_batch), bshapes.keys())
    cshapes = cache_shapes(cfg, shape)
    cspecs = cache_specs(cfg, mesh, shape.global_batch)
    rules = dist_ctx.default_rules(cfg, mesh, shape.global_batch)
    dp = dp_axes(mesh)
    b_ax = dp if shape.global_batch % dp_size(mesh) == 0 else None
    vdiv = cfg.vocab_size % mesh.shape.get("model", 1) == 0
    pos_shape = _sds((shape.global_batch,), jnp.int32)

    def step(params, batch, caches, cur_pos):
        logits, new_caches = decode_step(params, cfg, batch, caches, cur_pos)
        return logits, new_caches

    in_sh = (
        to_named(pspecs, mesh),
        to_named(bspecs, mesh),
        to_named(cspecs, mesh),
        NamedSharding(mesh, P(b_ax)),
    )
    out_sh = (
        NamedSharding(mesh, P(b_ax, "model" if vdiv else None)),
        to_named(cspecs, mesh),
    )
    # Donate the caches: the updated cache aliases the input buffer instead
    # of doubling decode memory.
    fn = _CtxJit(
        jax.jit(step, in_shardings=in_sh, out_shardings=out_sh, donate_argnums=(2,)),
        mesh,
        rules,
    )
    return BuiltStep(fn, (pshapes, bshapes, cshapes, pos_shape), in_sh, out_sh, rules)


def build_step(cfg: ModelConfig, mesh: Mesh, shape: ShapeConfig, **kw) -> BuiltStep:
    if shape.kind == "train":
        return build_train_step(cfg, mesh, shape, **kw)
    if shape.kind == "prefill":
        return build_prefill_step(cfg, mesh, shape)
    return build_decode_step(cfg, mesh, shape)
