"""Partitioned PK/FK join probe as a Pallas TPU kernel (dimension tables).

The paper's §3.2.1 partitioned join — `MR[s->id]` direct array access — is a
gather.  For *dimension-table* builds that fit VMEM (region/nation/part-
class tables; K ≤ a few thousand), the TPU-native probe keeps the whole
parent table VMEM-resident across all grid steps and performs the gather as
a one-hot × table matmul on the MXU, transposed so that rows run along the
128 lanes:

    out^T[C, 128] = table^T[C, K] @ onehot(fk)^T[K, 128]

This is deliberately *not* a scalar hash probe: the MXU contraction is the
idiomatic TPU spelling of K-way selection, and it fuses with downstream
arithmetic in the same VMEM tile.  Large parents use XLA's native gather
outside the kernel (`compile.py` pk_gather path).

Layout: the keys stream as lane-dense `(tile / 128, 128)` blocks, the
table is padded to `(8k, 128k)` (padded keys hold zero rows, so an
out-of-range key reads zeros) and the output is `(C, n)`, one lane-dense
row per column.  The contraction runs at f32 HIGHEST precision, so the
gathered values are exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.pipeline import LANES, lanes, resolve_interpret, tile_rows


def _kernel(fk_ref, table_ref, out_ref, *, rows: int):
    tbl = table_ref[...]                  # (C8, K128) f32 — VMEM resident
    keys = jax.lax.broadcasted_iota(jnp.int32, (tbl.shape[1], LANES), 0)
    for r in range(rows):                 # static: one 128-lane row of keys
        onehot = (keys == fk_ref[r:r + 1, :]).astype(jnp.float32)
        out_ref[:, r * LANES:(r + 1) * LANES] = jnp.dot(
            tbl, onehot, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def gather_join(fk: jax.Array, table: jax.Array, *, tile: int = 1024,
                interpret: "bool | None" = None) -> jax.Array:
    """out[i, :] = table[fk[i], :] (out-of-range fk rows return zeros).

    fk: (n,) int32; table: (K, C) float32.  Returns (n, C) float32.
    """
    rows = tile_rows(tile)
    n = fk.shape[0]
    k, c = table.shape
    n_t = -(-max(n, 1) // tile) * tile
    fk = jnp.pad(fk.astype(jnp.int32), (0, n_t - n), constant_values=-1)
    c8, k128 = -(-c // 8) * 8, -(-k // LANES) * LANES
    table_t = jnp.pad(table.astype(jnp.float32).T, ((0, c8 - c),
                                                    (0, k128 - k)))
    out = pl.pallas_call(
        functools.partial(_kernel, rows=rows),
        grid=(n_t // tile,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((c8, k128), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((c8, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((c8, n_t), jnp.float32),
        interpret=resolve_interpret(interpret),
        name="gather_join",
    )(lanes(fk, n_t), table_t)
    return out[:c, :n].T
