"""Mixture-of-Experts with SORT-BASED (reordered) dispatch.

Paper tie-in (DESIGN.md §5): the token->expert routing matrix is a sparse
matrix. This layer applies the paper's machinery to it:
  * `sorted` dispatch — tokens are PERMUTED by expert id (argsort): the
    reordering. Contiguous expert segments = dense blocks, exactly the
    block-locality argument of §4 applied to expert compute on the MXU.
  * capacity clipping — per-expert slot count C is the nnz-balanced
    schedule (paper Listing 5): every expert (processor) gets the same
    number of slots (nnz); overflow tokens are dropped like the paper's
    balanced panels bound max_load.
  * the nnz load-imbalance metric LI = max_load/fair_load (§6.1) is
    computed on the raw routing every step and returned as a metric.
  * `onehot` dispatch — the unreordered baseline (GShard-style dense
    one-hot einsum) for the ablation in benchmarks/moe_dispatch.

Expert parallelism: experts sharded over `ep_axis` (mesh "model"); tokens
arrive sequence-sharded over the same axis; dispatch buffers move through
one all_to_all each way. Single-device path (smoke tests) runs the same
body with no collectives.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .common import init_linear


def init_moe(key, d_model, cfg, dtype=jnp.float32):
    """cfg: MoEConfig. Expert weights stacked on a leading E axis."""
    ks = jax.random.split(key, 4)
    e, dff = cfg.num_experts, cfg.d_ff_expert
    scale = float(1.0 / np.sqrt(d_model))
    return {
        "router": init_linear(ks[0], d_model, e, False, dtype),
        "w_gate": scale * jax.random.truncated_normal(ks[1], -2, 2, (e, d_model, dff), dtype),
        "w_up": scale * jax.random.truncated_normal(ks[2], -2, 2, (e, d_model, dff), dtype),
        "w_down": float(1.0 / np.sqrt(dff)) * jax.random.truncated_normal(
            ks[3], -2, 2, (e, dff, d_model), dtype),
    }


def _route(params, x_flat, num_experts, top_k):
    """Returns (gates [n,k], experts [n,k], probs [n,E])."""
    logits = (x_flat.astype(jnp.float32) @ params["router"]["w"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, experts, probs


# public alias: repro.workloads lowers this routing decision to sparse
# dispatch/combine matrices (workloads/sources.py mirrors it in numpy)
route = _route


def _aux_loss(probs, experts, num_experts):
    """Switch-style load-balancing loss + the paper's LI metric."""
    n, _ = probs.shape
    onehot = jax.nn.one_hot(experts[:, 0], num_experts)  # primary expert
    f = onehot.mean(0)                                   # fraction per expert
    p = probs.mean(0)
    aux = num_experts * jnp.sum(f * p)
    counts = jnp.zeros((num_experts,), jnp.float32).at[experts.reshape(-1)].add(1.0)
    li = counts.max() / jnp.maximum(counts.mean(), 1e-9)  # paper §6.1
    return aux, li, counts


def _expert_ffn(buf, w_gate, w_up, w_down):
    """buf [E_loc, C, d] -> [E_loc, C, d] (SwiGLU per expert)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * \
        jnp.einsum("ecd,edf->ecf", buf, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _moe_body(params, x, *, moe_cfg, ep_axis: Optional[str], ep_size: int,
              fsdp_axis: Optional[str] = None, fsdp_size: int = 1):
    """x: [b, s, d] LOCAL tokens. Returns (y, metrics).

    Expert weights arrive FSDP-sharded on their d_model/d_ff dim over
    `fsdp_axis` and are all-gathered on use (grads reduce-scatter back via
    AD) — same memory/comm pattern as the dense layers' FSDP."""
    if fsdp_axis is not None and fsdp_size > 1:
        params = dict(params,
                      w_gate=jax.lax.all_gather(params["w_gate"], fsdp_axis,
                                                axis=1, tiled=True),
                      w_up=jax.lax.all_gather(params["w_up"], fsdp_axis,
                                              axis=1, tiled=True),
                      w_down=jax.lax.all_gather(params["w_down"], fsdp_axis,
                                                axis=2, tiled=True))
    b, s, d = x.shape
    e, k = moe_cfg.num_experts, moe_cfg.top_k
    n = b * s
    x_flat = x.reshape(n, d)
    gates, experts, probs = _route(params, x_flat, e, k)
    aux, li, counts = _aux_loss(probs, experts, e)

    # capacity = nnz-balanced schedule (paper Listing 5 analogue)
    cap = int(np.ceil(n * k * moe_cfg.capacity_factor / e / 8)) * 8

    # ---- sorted (reordered) dispatch ----
    ef = experts.reshape(-1)                       # [n*k]
    tok = jnp.repeat(jnp.arange(n), k)
    gf = gates.reshape(-1)
    if moe_cfg.dispatch == "sorted":
        order = jnp.argsort(ef)                    # the reordering permutation
        ef_s, tok_s, gf_s = ef[order], tok[order], gf[order]
        # rank within expert segment
        seg_start = jnp.searchsorted(ef_s, ef_s, side="left")
        rank = jnp.arange(n * k) - seg_start
    else:  # onehot baseline: rank via cumsum over unsorted assignments
        onehot_full = jax.nn.one_hot(ef, e, dtype=jnp.int32)
        rank = (jnp.cumsum(onehot_full, axis=0) - 1)[jnp.arange(n * k), ef]
        ef_s, tok_s, gf_s = ef, tok, gf
    keep = rank < cap
    slot = jnp.where(keep, ef_s * cap + rank, e * cap)   # drop -> scratch row
    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].set(x_flat[tok_s])
    buf = buf[:-1].reshape(e, cap, d)

    if ep_axis is not None and ep_size > 1:
        # [E, C, d] -> [E/M, M*C, d]: each rank keeps its experts' slots
        buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                                 tiled=True)
    y_buf = _expert_ffn(buf, params["w_gate"], params["w_up"], params["w_down"])
    if ep_axis is not None and ep_size > 1:
        y_buf = jax.lax.all_to_all(y_buf, ep_axis, split_axis=1, concat_axis=0,
                                   tiled=True)

    # combine: gather each assignment's slot output, weight, sum over k
    y_flat = jnp.concatenate([y_buf.reshape(e * cap, d),
                              jnp.zeros((1, d), y_buf.dtype)])  # scratch row
    contrib = y_flat[slot] * (gf_s * keep)[:, None]
    y = jnp.zeros((n, d), x.dtype).at[tok_s].add(contrib.astype(x.dtype))

    drop_frac = 1.0 - keep.mean()
    metrics = {"aux_loss": aux, "router_li": li, "drop_frac": drop_frac}
    return y.reshape(b, s, d), metrics


def moe_layer(params, x, moe_cfg, mesh=None, ep_axis="model",
              dp_axes=("data",)):
    """x: [B, S, d] GLOBAL (under jit+mesh) or local (mesh=None).

    With a mesh: shard_map over (dp_axes x ep_axis); tokens are
    sequence-sharded over ep_axis when S divides, giving each device
    n = B_l * S/M tokens to route (DESIGN.md §4).
    """
    if mesh is None:
        return _moe_body(params, x, moe_cfg=moe_cfg, ep_axis=None, ep_size=1)

    from jax.sharding import PartitionSpec as P

    from ...distributed import sharding as _SH

    ep_size = mesh.shape[ep_axis]
    fsdp_axis = ("data" if (_SH.MOE_FSDP and "data" in mesh.axis_names)
                 else None)
    fsdp_size = mesh.shape[fsdp_axis] if fsdp_axis else 1
    s = x.shape[1]
    seq_shard = (s % ep_size == 0) and (s // ep_size >= 1) and s > 1
    xspec = P(dp_axes, ep_axis if seq_shard else None, None)
    wspec = {"router": {"w": P()},
             "w_gate": P(ep_axis, fsdp_axis, None),
             "w_up": P(ep_axis, fsdp_axis, None),
             "w_down": P(ep_axis, None, fsdp_axis)}

    def body(p, xl):
        y, metrics = _moe_body(p, xl, moe_cfg=moe_cfg, ep_axis=ep_axis,
                               ep_size=ep_size, fsdp_axis=fsdp_axis,
                               fsdp_size=fsdp_size)
        # metrics are per-shard; average over the whole mesh
        metrics = {k: jax.lax.pmean(jax.lax.pmean(v, ep_axis), dp_axes)
                   for k, v in metrics.items()}
        return y, metrics

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(wspec, xspec),
                      out_specs=(xspec, {"aux_loss": P(), "router_li": P(),
                                         "drop_frac": P()}),
                      check_vma=False)
    return f(params, x)
