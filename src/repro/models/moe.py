"""Mixture-of-Experts FFN.

Implementation: capacity-bounded grouped compute with *local* routing.
Tokens are routed per data-shard (inside `shard_map` over the batch axes),
sorted by expert id, and each expert processes a fixed-capacity slice of
the sorted token stream — all static shapes, no host round trips.  Expert
FFN width is sharded over the `model` axis (tensor-parallel experts), so
the only collective is the same per-layer psum a dense FFN needs; the
compiled FLOPs are capacity_factor × active-expert FLOPs (the roofline
table reports MODEL_FLOPS as 6·N_active·D and the ratio exposes the
capacity slack).

An expert-parallel all-to-all variant is the recorded §Perf hillclimb for
the MoE-bound cells (see EXPERIMENTS.md).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import dense_init


def moe_init(key, cfg, dtype):
    e, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 8)
    p = {
        "w_router": dense_init(ks[0], (d, e), jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, f), dtype, scale_axis=1),
        "w_up": dense_init(ks[2], (e, d, f), dtype, scale_axis=1),
        "w_down": dense_init(ks[3], (e, f, d), dtype, scale_axis=1),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * (cfg.moe_d_ff or cfg.d_ff)
        p["shared"] = {
            "w_gate": dense_init(ks[4], (d, fs), dtype),
            "w_up": dense_init(ks[5], (d, fs), dtype),
            "w_down": dense_init(ks[6], (fs, d), dtype, scale_axis=0),
        }
    return p


def _moe_local(x, p, *, topk: int, capacity: int, tp_axis: str | None,
               unroll: bool = False):
    """x: (N, D) local tokens. Expert weights locally (E, D, F_local)."""
    n, d = x.shape
    e = p["w_router"].shape[1]
    logits = x.astype(jnp.float32) @ p["w_router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, ids = jax.lax.top_k(probs, topk)                 # (N, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    flat_ids = ids.reshape(-1).astype(jnp.int32)             # (N*k,)
    flat_tok = jnp.repeat(jnp.arange(n, dtype=jnp.int32), topk)
    flat_w = gate_w.reshape(-1).astype(jnp.float32)
    order = jnp.argsort(flat_ids)
    s_ids = jnp.pad(flat_ids[order], (0, capacity), constant_values=-1)
    s_tok = jnp.pad(flat_tok[order], (0, capacity))
    s_w = jnp.pad(flat_w[order], (0, capacity))
    counts = jnp.bincount(flat_ids, length=e)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(counts).astype(jnp.int32)])

    import os

    # §Perf knob: experts per scan step.  group=1 scatters the (N, D)
    # accumulator once per expert (E full traversals); larger groups batch
    # G experts' contributions into one scatter (E/G traversals).
    group = int(os.environ.get("REPRO_MOE_GROUP", "1"))
    group = max(1, min(group, e))
    while e % group:
        group -= 1

    def body(acc, einp):
        eids, wgs, wus, wds = einp
        # one gather for the whole group: the backward of this gather is a
        # single scatter into dx (instead of one per expert) — with group=1
        # this degenerates to the per-expert baseline.
        idx_l, eid_l, w_l = [], [], []
        for j in range(group):
            start = offsets[eids[j]]
            idx_l.append(jax.lax.dynamic_slice(s_tok, (start,), (capacity,)))
            eid_l.append(jax.lax.dynamic_slice(s_ids, (start,), (capacity,)))
            w_l.append(jax.lax.dynamic_slice(s_w, (start,), (capacity,)))
        cat_idx = jnp.concatenate(idx_l)
        xg = x[cat_idx]                                   # (G·C, D)
        ys = []
        for j in range(group):
            valid = (eid_l[j] == eids[j])
            xe = xg[j * capacity:(j + 1) * capacity] \
                * valid[:, None].astype(x.dtype)
            h = jax.nn.silu(xe @ wgs[j]) * (xe @ wus[j])
            ys.append((h @ wds[j]).astype(jnp.float32)
                      * (w_l[j] * valid)[:, None])
        return acc.at[cat_idx].add(jnp.concatenate(ys)), None

    acc0 = jnp.zeros((n, d), jnp.float32)
    eidx = jnp.arange(e, dtype=jnp.int32).reshape(e // group, group)
    stack = lambda w: w.reshape(e // group, group, *w.shape[1:])
    xs = (eidx, stack(p["w_gate"]), stack(p["w_up"]), stack(p["w_down"]))
    if unroll:
        # straight-line expert loop: exact cost accounting for the dry-run
        # probes (XLA counts while-loop bodies once)
        acc = acc0
        for gstep in range(e // group):
            acc, _ = body(acc, jax.tree.map(lambda t: t[gstep], xs))
    else:
        acc, _ = jax.lax.scan(body, acc0, xs)

    if "shared" in p:
        sp = p["shared"]
        h = jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])
        acc = acc + (h @ sp["w_down"]).astype(jnp.float32)

    if tp_axis is not None:
        acc = jax.lax.psum(acc, tp_axis)   # partial sums over F shards
    return acc.astype(x.dtype)


def moe_ffn(x, p, cfg, ctx):
    """x: (B, S, D). ctx: repro.models.sharding.Ctx (mesh optional)."""
    b, s, d = x.shape

    def run(xl, pl_):
        n = xl.shape[0] * xl.shape[1]
        cap = int(np.ceil(cfg.capacity_factor * n * cfg.topk
                          / max(cfg.n_experts, 1)))
        cap = max(8, -(-cap // 8) * 8)
        y = _moe_local(xl.reshape(n, d), pl_, topk=cfg.topk, capacity=cap,
                       tp_axis=ctx.tp_axis if ctx.mesh is not None else None,
                       unroll=cfg.unroll)
        return y.reshape(xl.shape)

    if ctx.mesh is None:
        return run(x, p)

    from jax.sharding import PartitionSpec as P

    dp = ctx.dp_axes
    if b % ctx.dp_size != 0:
        # global_batch=1 decode (long_500k): tokens replicate across the
        # batch axes; expert FFN stays TP-sharded over `model`.
        dp = None
    specs_p = {
        "w_router": P(None, None),
        "w_gate": P(None, None, ctx.tp_axis),
        "w_up": P(None, None, ctx.tp_axis),
        "w_down": P(None, ctx.tp_axis, None),
    }
    if "shared" in p:
        specs_p["shared"] = {
            "w_gate": P(None, ctx.tp_axis),
            "w_up": P(None, ctx.tp_axis),
            "w_down": P(ctx.tp_axis, None),
        }
    return jax.shard_map(
        run, mesh=ctx.mesh,
        in_specs=(P(dp, None, None), specs_p),
        out_specs=P(dp, None, None),
        check_vma=False,
    )(x, p)
