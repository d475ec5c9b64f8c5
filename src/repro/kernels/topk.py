"""Masked top-k Pallas TPU kernel (ORDER BY <metric> DESC LIMIT k).

TPU adaptation of the paper's sort operator for limit queries (Q3/Q10/Q18):
a global sort is wasteful when only k rows survive.  Each grid step reduces
a lane-dense `(tile / 128, 128)` VMEM block to its local top-k by iterative
max-extraction (k is small and static), building the k winners in one
`(1, 128k)` vector row that is stored whole: Mosaic refuses scalar stores
to VMEM.  The `(num_tiles, k)` partials are then reduced by
`jax.lax.top_k` outside the kernel, which is O(num_tiles·k) — negligible.
Ties go to the lowest row index, as in `jax.lax.top_k`.
"""
from __future__ import annotations

import functools

import jax
import numpy as np
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.pipeline import LANES, lanes, resolve_interpret, tile_rows

_NEG = np.float32(-3.0e38)  # python-level constant: not a captured tracer


def _kernel(vals_ref, mask_ref, outv_ref, outi_ref, *, k: int, rows: int):
    base = pl.program_id(0) * rows * LANES
    shape = (rows, LANES)
    pos = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
           + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    slot = jax.lax.broadcasted_iota(jnp.int32, outv_ref.shape, 1)

    def take(j, carry):
        v, topv, topi = carry
        m = jnp.max(v)
        at = jnp.min(jnp.where(v == m, pos, rows * LANES))
        topv = jnp.where(slot == j, m, topv)
        topi = jnp.where(slot == j, base + at, topi)
        return jnp.where(pos == at, _NEG, v), topv, topi

    v = jnp.where(mask_ref[...] != 0, vals_ref[...], _NEG)
    _, topv, topi = jax.lax.fori_loop(
        0, k, take, (v, jnp.full(outv_ref.shape, _NEG, jnp.float32),
                     jnp.full(outi_ref.shape, -1, jnp.int32)))
    outv_ref[...] = topv
    outi_ref[...] = topi


@functools.partial(jax.jit, static_argnames=("k", "tile", "interpret"))
def masked_topk(vals: jax.Array, mask: jax.Array, k: int, *,
                tile: int = 4096, interpret: "bool | None" = None
                ) -> tuple[jax.Array, jax.Array]:
    """Top-k values of `vals` where `mask`, with their row indices.

    Returns (values (k,), indices (k,)); if fewer than k rows are valid the
    tail carries -inf sentinels and index -1.
    """
    rows = tile_rows(tile)
    if k > tile:
        raise ValueError(f"k={k} exceeds the block of {tile} rows")
    n = vals.shape[0]
    n_t = -(-max(n, 1) // tile) * tile
    steps = n_t // tile
    kp = -(-k // LANES) * LANES
    out_spec = pl.BlockSpec((pl.Squeezed(), 1, kp), lambda i: (i, 0, 0))
    pv, pi = pl.pallas_call(
        functools.partial(_kernel, k=k, rows=rows),
        grid=(steps,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))] * 2,
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((steps, 1, kp), jnp.float32),
            jax.ShapeDtypeStruct((steps, 1, kp), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
        name="masked_topk",
    )(lanes(vals.astype(jnp.float32), n_t), lanes(mask, n_t))

    flatv, flati = pv[:, 0, :k].reshape(-1), pi[:, 0, :k].reshape(-1)
    topv, at = jax.lax.top_k(flatv, k)
    topi = jnp.where(topv <= _NEG, -1, flati[at])
    return topv, topi
