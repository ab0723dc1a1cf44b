"""Mixture-of-Experts FFN with sort-based capacity dispatch.

Design for TPU + GSPMD (see DESIGN.md): activations between blocks are
replicated over the 'model' mesh axis (standard TP), experts are sharded
over 'model' (EP on the same axis). Dispatch is *local selection*, not
all_to_all: a scatter builds the (E, C, D) expert buffer, sharded on E, so
each shard materializes only its experts' tokens; the combine scatter-adds
back to the replicated activation, which GSPMD completes with the same
all-reduce a dense TP FFN needs anyway.

FLOPs honesty: dispatch/combine are gathers/scatters (O(bytes), ~0 FLOPs);
expert compute is E_local × C × (GLU matmuls) ≈ tokens × top_k ×
capacity_factor × per-expert-FFN — matching 6·N_active·D within the
capacity slack, unlike dense one-hot dispatch (which would inflate
HLO_FLOPs ~E/top_k x).

Capacity-overflow tokens are dropped (GShard semantics); the router's
aux load-balancing loss (Switch-style) keeps drop rates low in training.
"""
from __future__ import annotations

import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed import ctx as dist_ctx
from .layers import activation


def init_moe_params(key, d_model: int, d_ff: int, n_experts: int, dtype) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std_in = 1.0 / math.sqrt(d_model)
    std_out = 1.0 / math.sqrt(d_ff)
    return {
        "router": (jax.random.normal(k1, (d_model, n_experts), jnp.float32) * std_in),
        "wi_gate": (jax.random.normal(k2, (n_experts, d_model, d_ff), dtype) * std_in).astype(dtype),
        "wi_up": (jax.random.normal(k3, (n_experts, d_model, d_ff), dtype) * std_in).astype(dtype),
        "wo": (jax.random.normal(k4, (n_experts, d_ff, d_model), dtype) * std_out).astype(dtype),
    }


def capacity_for(n_tokens: int, n_experts: int, top_k: int, capacity_factor: float) -> int:
    """Per-expert capacity. Rows align to the MXU (128) in the training
    regime, but the floor scales down for small token counts (decode:
    T_local of a few tokens would otherwise pad every expert to 128 rows —
    measured 100x useful/HLO waste on the MoE decode cells)."""
    c = int(math.ceil(n_tokens * top_k * capacity_factor / n_experts))
    if n_tokens >= 1024:
        return max(((c + 127) // 128) * 128, 128)  # MXU-aligned rows
    return max(((c + 7) // 8) * 8, 8)  # decode-sized: sublane-aligned


def _dispatch_compute_combine(xf, router, wi_gate, wi_up, wo, *, top_k, cap, act,
                              e_first: int = 0, e_local: Optional[int] = None):
    """Shared core: route + capacity-dispatch xf (T, D) to experts
    [e_first, e_first + e_local), run the GLU FFN, weighted-combine back.
    Returns (y (T, D) partial over the expert range, aux f32)."""
    t, d = xf.shape
    e = router.shape[1]
    e_local = e if e_local is None else e_local

    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E) f32
    gates, idx = jax.lax.top_k(probs, top_k)  # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # Switch-style aux load-balancing loss (local tokens).
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (t * top_k)
    aux = e * jnp.sum(me * ce)

    flat_e = idx.reshape(-1)
    order = jnp.argsort(flat_e)
    se = flat_e[order]
    starts = jnp.searchsorted(se, jnp.arange(e, dtype=se.dtype))
    pos_in_e = jnp.arange(t * top_k, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    le = se.astype(jnp.int32) - e_first
    keep = (pos_in_e < cap) & (le >= 0) & (le < e_local)
    token_of = (order // top_k).astype(jnp.int32)
    gate_of = gates.reshape(-1)[order]

    slot = jnp.clip(le, 0, e_local - 1) * cap + jnp.clip(pos_in_e, 0, cap - 1)
    slot = jnp.where(keep, slot, e_local * cap)  # overflow slot (discarded)
    buf = jnp.zeros((e_local * cap + 1, d), xf.dtype).at[slot].set(xf[token_of])
    buf = buf[: e_local * cap].reshape(e_local, cap, d)
    buf = dist_ctx.constrain("moe_buf", buf) if e_local == e else buf

    g = activation(jnp.einsum("ecd,edf->ecf", buf, wi_gate), act)
    u = jnp.einsum("ecd,edf->ecf", buf, wi_up)
    out_buf = jnp.einsum("ecf,efd->ecd", g * u, wo)
    out_buf = dist_ctx.constrain("moe_buf", out_buf) if e_local == e else out_buf

    flat_out = out_buf.reshape(e_local * cap, d)
    picked = flat_out[jnp.clip(slot, 0, e_local * cap - 1)]
    contrib = picked * jnp.where(keep, gate_of, 0.0).astype(picked.dtype)[:, None]
    y = jnp.zeros((t, d), xf.dtype).at[token_of].add(contrib)
    return y, aux


def moe_ffn(
    params: dict,
    x,
    *,
    top_k: int,
    capacity_factor: float,
    act: str,
    constrain_buf: Optional[Callable] = None,
) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y (B, S, D), aux_loss scalar f32).

    Under a mesh context with a >1 'model' axis and divisible experts, the
    expert-parallel shard_map path runs: routing + dispatch are LOCAL per
    (dp, model) shard (each model shard selects tokens for ITS experts from
    its dp-local, model-replicated activations) and the only collective is
    the per-layer psum over 'model' that dense TP FFNs pay anyway. Without
    it, GSPMD lowers the global argsort-dispatch into cross-device sorts —
    measured 9.4 s/step of collectives on moonshot train_4k."""
    b, s, d = x.shape
    e = params["router"].shape[1]

    mesh = dist_ctx.current_mesh()
    if mesh is not None:
        nm = mesh.shape.get("model", 1)
        dp_names = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
        dp_sz = int(np.prod([mesh.shape[a] for a in dp_names])) if dp_names else 1
        if nm > 1 and e % nm == 0 and b % max(dp_sz, 1) == 0:
            return _moe_ffn_shard_map(
                params, x, top_k=top_k, capacity_factor=capacity_factor,
                act=act, mesh=mesh, dp_names=dp_names,
            )

    t = b * s
    cap = capacity_for(t, e, top_k, capacity_factor)
    y, aux = _dispatch_compute_combine(
        x.reshape(t, d), params["router"], params["wi_gate"], params["wi_up"],
        params["wo"], top_k=top_k, cap=cap, act=act,
    )
    return y.reshape(b, s, d), aux


def _moe_ffn_shard_map(params, x, *, top_k, capacity_factor, act, mesh, dp_names):
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    b, s, d = x.shape
    e = params["router"].shape[1]
    nm = mesh.shape["model"]
    dp_sz = int(np.prod([mesh.shape[a] for a in dp_names])) if dp_names else 1
    t_loc = (b // dp_sz) * s
    cap = capacity_for(t_loc, e, top_k, capacity_factor)
    bspec = dp_names if dp_names else None

    def inner(x_loc, router, wg, wu, wo):
        e_loc = wg.shape[0]
        m_idx = jax.lax.axis_index("model")
        bl, sl, dl = x_loc.shape
        y, aux = _dispatch_compute_combine(
            x_loc.reshape(bl * sl, dl), router, wg, wu, wo,
            top_k=top_k, cap=cap, act=act,
            e_first=m_idx * e_loc, e_local=e_loc,
        )
        y = jax.lax.psum(y, "model")  # the TP combine a dense FFN pays too
        if dp_names:
            aux = jax.lax.pmean(aux, dp_names)
        return y.reshape(bl, sl, dl), aux

    return shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            P(bspec, None, None),
            P(None, None),
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False,
    )(x, params["router"], params["wi_gate"], params["wi_up"], params["wo"])
