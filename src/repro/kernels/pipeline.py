"""The selective-pipeline Pallas TPU kernel: filter -> compact -> aggregate.

The TPU-native form of the paper's specialized query loop (Fig 4b / Q1/Q6
after all optimizations): one pass over the fact table that evaluates the
selection predicate, ranks the surviving rows, and accumulates every
aggregate, with no intermediate materialized in HBM.  Every public entry
point is a configuration of the one kernel:

  * `compact` / `compact_translate` -- stream compaction of a mask: the
    valid row ids packed to the front of a static-capacity buffer (the
    contract of `backend.compact`), optionally with the CSR key->slot
    translation `slot_of[row]` that a compact-aware `pk_gather` probes;
  * `compact_pred` -- the same with the predicate evaluated in-kernel from
    named column blocks and parameter scalars (filter -> compact fused);
  * `filter_agg` -- masked grouped sums;
  * `selective_filter_agg` -- all of it in one pass.

Layout (what the TPU compiler accepts and what fits HBM):

  * **lane-dense columns** -- every 1-D column is padded and reshaped to
    `(n_t / 128, 128)`, and the grid walks `(rows, 128)` blocks of it.  A
    `(n, 1)` operand would pad its minor dimension to 128 lanes in HBM and
    multiply its footprint 128-fold;
  * **scalars in SMEM** -- parameters arrive as `(1,)` SMEM blocks, and the
    running valid count (which doubles as the next block's output offset;
    the grid is sequential) is a `(1,)` SMEM output.  Mosaic refuses scalar
    stores to VMEM;
  * **one 128-lane row at a time** -- a `fori_loop` over the block's rows
    evaluates the tile functions on `(1, 128)` slices.  Within a row:
      - the inclusive prefix count is `mask @ triu(ones)` on the MXU
        (Mosaic has no cumsum; 0/1 operands make it exact);
      - packing needs no scatter: output lane `t` holds valid element
        `j = (t - off) mod 128`, whose source lane is the number of lanes
        with prefix <= j -- a compare matrix contracted with ones, again
        exact on the MXU, already rotated to the store offset;
      - the packed row lands in the VMEM-resident lane-dense `idx` buffer
        with two masked read-modify-write stores (rows `off // 128` and the
        next); a block at or past the capacity stores nothing;
      - grouped sums are `values (A, 128) . onehot (G, 128)^T` on the MXU at
        full f32 precision, accumulated into a resident `(A, G)` block.

Overflow semantics: the returned count is the exact predicate total (it
may exceed `capacity`: the caller's overflow signal); slots past the
capacity are never returned, and pad slots are zero (in `[0, n)`, safe for
clamping gathers).

`interpret=None` (the default everywhere) compiles the kernel on a TPU
backend and runs the Pallas interpreter elsewhere (CPU tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_HI = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))          # contract the lane axis of both


def resolve_interpret(interpret: "bool | None") -> bool:
    """None = interpret only when JAX's backend is not a TPU."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


def _row_kernel(*refs, names, n_scalars: int, pred_fn, vals_fn, gidx_fn,
                n_rows: int, rows: int, n_vals: int, capacity: int,
                translate: bool):
    """refs = [col_0..col_{C-1}, scalar_0..scalar_{S-1},
               cnt, (sums), (idx), (slot)]"""
    step = pl.program_id(0)
    ncols = len(names)
    col_refs = refs[:ncols]
    scalars = [refs[ncols + i][0] for i in range(n_scalars)]
    out = list(refs[ncols + n_scalars:])
    cnt_ref = out.pop(0)
    sums_ref = out.pop(0) if n_vals else None
    idx_ref = out.pop(0) if capacity else None
    slot_ref = out.pop(0) if translate else None

    @pl.when(step == 0)
    def _init():
        cnt_ref[0] = 0
        if sums_ref is not None:
            sums_ref[...] = jnp.zeros_like(sums_ref)
        if idx_ref is not None:
            idx_ref[...] = jnp.zeros_like(idx_ref)

    sq = (LANES, LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, sq, 0)
    triu = (sub <= jax.lax.broadcasted_iota(jnp.int32, sq, 1)
            ).astype(jnp.float32)
    ones = jnp.ones((1, LANES), jnp.float32)

    def row(r, off):
        cols = {nm: ref[pl.ds(r, 1), :] for nm, ref in zip(names, col_refs)}
        base = (step * rows + r) * LANES
        m = jnp.broadcast_to(jnp.asarray(pred_fn(cols, scalars)),
                             (1, LANES)).astype(bool)
        m = m & (base + lane < n_rows)            # padded tail rows
        mf = m.astype(jnp.float32)
        k = jnp.sum(m.astype(jnp.int32))
        if sums_ref is not None:
            vs = [jnp.broadcast_to(jnp.asarray(v, jnp.float32), (1, LANES))
                  for v in vals_fn(cols, scalars)]
            a_pad = sums_ref.shape[0]
            if a_pad > len(vs):
                vs.append(jnp.zeros((a_pad - len(vs), LANES), jnp.float32))
            vals = jnp.concatenate(vs, axis=0) * mf
            g = jnp.zeros((1, LANES), jnp.int32) if gidx_fn is None \
                else jnp.broadcast_to(jnp.asarray(gidx_fn(cols, scalars),
                                                  jnp.int32), (1, LANES))
            groups = jax.lax.broadcasted_iota(
                jnp.int32, (sums_ref.shape[1], LANES), 0)
            onehot = (groups == g).astype(jnp.float32)
            sums_ref[...] += jax.lax.dot_general(
                vals, onehot, _NT, precision=_HI,
                preferred_element_type=jnp.float32)
        if idx_ref is not None or slot_ref is not None:
            prefix = jnp.dot(mf, triu, preferred_element_type=jnp.float32
                             ).astype(jnp.int32)          # inclusive
        if slot_ref is not None:
            slot_ref[pl.ds(r, 1), :] = jnp.where(m, off + prefix - 1, -1)
        if idx_ref is not None:
            @pl.when(off < capacity)
            def _store():
                s = off % LANES
                q = off // LANES
                j = (sub - s) & (LANES - 1)  # element held by output lane
                src = jax.lax.dot_general(
                    ones, (prefix <= j).astype(jnp.float32), _NT,
                    preferred_element_type=jnp.float32).astype(jnp.int32)
                ids = base + src
                jt = (lane - s) & (LANES - 1)
                here = (lane >= s) & (jt < k)
                nxt = (lane < s) & (jt < k)
                cur = idx_ref[pl.ds(q, 1), :]
                idx_ref[pl.ds(q, 1), :] = jnp.where(here, ids, cur)
                cur = idx_ref[pl.ds(q + 1, 1), :]
                idx_ref[pl.ds(q + 1, 1), :] = jnp.where(nxt, ids, cur)
        return off + k

    cnt_ref[0] = jax.lax.fori_loop(0, rows, row, cnt_ref[0])


def tile_rows(tile: int) -> int:
    """128-lane rows in a block of `tile` elements.  A TPU block holds
    whole (8, 128) vreg tiles, so `tile` must be a multiple of 1024."""
    if tile <= 0 or tile % (8 * LANES):
        raise ValueError(f"tile={tile} is not a positive multiple of "
                         f"{8 * LANES}")
    return tile // LANES


def lanes(x, n_t: int):
    """(n,) column -> lane-dense (n_t / 128, 128), 32-bit, zero padded."""
    x = jnp.asarray(x)
    if x.dtype.itemsize != 4:
        x = x.astype(jnp.int32)
    return jnp.pad(x, (0, n_t - x.shape[0])).reshape(-1, LANES)


def _pipeline(cols: dict, scalars: list, pred_fn, vals_fn, gidx_fn,
              n_vals: int, n_groups: int, capacity: int, translate: bool,
              tile: int, interpret: "bool | None"):
    """The kernel launch behind every public entry point.  Returns
    [count, (sums (n_groups, n_vals)), (idx (capacity,)), (slot_of (n,))]."""
    names = list(cols)
    n = jnp.shape(cols[names[0]])[0]
    # small inputs shrink the block, down to the 8-row sublane minimum
    need = -(-max(n, 1) // LANES)
    rows = max(8, min(tile_rows(tile), -(-need // 8) * 8))
    block = rows * LANES
    n_t = -(-max(n, 1) // block) * block
    ins = [lanes(cols[nm], n_t) for nm in names]
    ins += [jnp.asarray(s).reshape(1) for s in scalars]
    row_spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    smem = pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM)
    in_specs = [row_spec] * len(names) + [smem] * len(scalars)
    out_shape = [jax.ShapeDtypeStruct((1,), jnp.int32)]
    out_specs = [smem]
    if n_vals:
        a_pad = -(-n_vals // 8) * 8
        g_pad = -(-n_groups // LANES) * LANES
        out_shape.append(jax.ShapeDtypeStruct((a_pad, g_pad), jnp.float32))
        out_specs.append(pl.BlockSpec((a_pad, g_pad), lambda i: (0, 0)))
    vmem = 0
    if capacity:
        # two spare rows: a store at offset < capacity touches rows q, q+1
        cap_rows = -(-capacity // LANES) + 2
        out_shape.append(jax.ShapeDtypeStruct((cap_rows, LANES), jnp.int32))
        out_specs.append(pl.BlockSpec((cap_rows, LANES), lambda i: (0, 0)))
        vmem = 2 * cap_rows * LANES * 4
    if translate:
        if not capacity:
            raise ValueError("translate requires a compaction capacity")
        out_shape.append(jax.ShapeDtypeStruct((n_t // LANES, LANES),
                                              jnp.int32))
        out_specs.append(row_spec)
    # the resident idx buffer is double-buffered; past the default scoped
    # VMEM, ask for what it needs plus room for the streamed blocks
    params = pltpu.CompilerParams(
        vmem_limit_bytes=vmem + (32 << 20)) if vmem > (8 << 20) else None
    res = pl.pallas_call(
        functools.partial(
            _row_kernel, names=names, n_scalars=len(scalars),
            pred_fn=pred_fn, vals_fn=vals_fn, gidx_fn=gidx_fn, n_rows=n,
            rows=rows, n_vals=n_vals, capacity=capacity,
            translate=translate),
        grid=(n_t // block,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=params,
        interpret=resolve_interpret(interpret),
        name="pipeline",
    )(*ins)
    res = list(res)
    out = [res.pop(0)[0]]
    if n_vals:
        out.append(res.pop(0)[:n_vals, :n_groups].T)
    if capacity:
        out.append(res.pop(0).reshape(-1)[:capacity])
    if translate:
        out.append(res.pop(0).reshape(-1)[:n])
    return out


@functools.partial(jax.jit, static_argnames=("capacity", "tile", "interpret",
                                             "translate"))
def compact(mask: jax.Array, capacity: int, *, tile: int = 4096,
            interpret: "bool | None" = None, translate: bool = False):
    """Single-pass `(idx int32[capacity], count int32)` over a boolean
    mask; with `translate=True` also returns `slot_of int32[n]` (-1 on
    invalid rows, else the row's compacted slot)."""
    cnt, *rest = _pipeline({"m": mask}, [], lambda c, s: c["m"] != 0, None,
                           None, 0, 0, capacity, translate, tile, interpret)
    return (rest[0], cnt, *rest[1:])


def compact_translate(mask: jax.Array, capacity: int, *, tile: int = 4096,
                      interpret: "bool | None" = None):
    """`compact` + the CSR key->slot translation vector, one pass."""
    return compact(mask, capacity, tile=tile, interpret=interpret,
                   translate=True)


def compact_pred(cols: dict, scalars: list, pred_fn, capacity: int, *,
                 tile: int = 4096, interpret: "bool | None" = None,
                 translate: bool = False):
    """Filter -> compact fused into one HBM pass: the predicate is
    evaluated in-kernel on column blocks.

    cols: {name: (n,) array} -- every column the predicate reads;
    scalars: list of () arrays -- runtime parameters, positionally
    matching what `pred_fn` expects;
    pred_fn(cols_tile, scalars) -> bool block, pure jnp elementwise.
    Returns the `compact` contract (+ `slot_of` when `translate`).
    """
    cnt, *rest = _pipeline(cols, scalars, pred_fn, None, None, 0, 0,
                           capacity, translate, tile, interpret)
    return (rest[0], cnt, *rest[1:])


@functools.partial(jax.jit, static_argnames=("n_groups", "tile", "interpret"))
def filter_agg(mask: jax.Array, gidx: jax.Array, vals: jax.Array,
               n_groups: int, *, tile: int = 4096,
               interpret: "bool | None" = None) -> jax.Array:
    """sum of `vals[i, a]` into group `gidx[i]` where `mask[i]`.

    mask: (n,) bool; gidx: (n,) int32; vals: (n, A) float32.
    Returns (n_groups, A) float32.
    """
    a = vals.shape[1]
    cols = {"m": mask, "g": gidx, **{f"v{i}": vals[:, i] for i in range(a)}}
    _cnt, sums = _pipeline(
        cols, [], lambda c, s: c["m"] != 0,
        lambda c, s: [c[f"v{i}"] for i in range(a)], lambda c, s: c["g"],
        a, n_groups, 0, False, tile, interpret)
    return sums


def selective_filter_agg(cols: dict, scalars: list, pred_fn, vals_fn,
                         gidx_fn, n_vals: int, n_groups: int,
                         capacity: int = 0, translate: bool = False, *,
                         tile: int = 4096, interpret: "bool | None" = None):
    """The whole selective pipeline in one kernel pass.

    cols: {name: (n,) array} -- every column any tile function reads;
    scalars: list of () arrays (runtime parameters);
    pred_fn(cols, scalars)  -> bool block          selection predicate
    vals_fn(cols, scalars)  -> list of n_vals f32 blocks (aggregate inputs)
    gidx_fn(cols, scalars)  -> int32 group-index block, or None (G=1)

    Returns (sums (n_groups, n_vals) f32, count int32[, idx int32[capacity]
    [, slot_of int32[n]]]): `count` is the exact number of predicate-true
    rows (> capacity = overflow); with `capacity > 0` the compacted row-id
    vector is emitted from the same pass, and `translate` adds the CSR
    key->slot vector over the input domain.
    """
    cnt, sums, *rest = _pipeline(cols, scalars, pred_fn, vals_fn, gidx_fn,
                                 n_vals, n_groups, capacity, translate, tile,
                                 interpret)
    return (sums, cnt, *rest)
