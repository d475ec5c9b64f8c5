"""Compact: selection-vector compaction to a static capacity bucket.

The mask-carrying execution model pays full-table cost in every operator
downstream of a selective predicate: a 0.2%-selectivity query still
gathers, sorts and segment-reduces over every row.  `Compact` converts the
frame to the dense, layout-specialized representation the paper's §3.2
argues for: `backend.compact(mask, capacity)` ranks the valid rows with a
cumsum and finds the row id of each of the *statically planned*
`capacity` output slots (JAX shapes must be static), then every column is
gathered down to `capacity` rows.  How the ids are found is the backend's
rank route, picked from the static shapes (`backend.compact_route`): a
binary search over the running count where the capacity is small against
the frame, a scatter of every valid row's id to its rank where the
search's dependent gathers would cost more.  Both give the same ids.
Downstream operators are oblivious — they see an ordinary, much smaller
Frame whose mask marks only the pad slots.

If more rows survive than the planner estimated, the surplus is dropped
from the index vector; the point's TRUE valid count is registered through
`StageCtx.note_compact` and surfaced (keyed by point id) as part of the
staged program's third output.  `CompiledQuery` compares each count with
its planned capacity: on overflow it re-executes the uncompacted fallback
plan (an estimate can only ever cost time), and either way the measured
counts feed the plan cache's adaptive capacity feedback.

Under `Settings.use_pallas` the XLA sequence (cumsum → rank → gather)
is replaced by the single-HBM-pass Pallas kernel
(`repro.kernels.pipeline`), and when the child is a Select whose
predicate is kernel-safe over an elementwise chain, predicate evaluation
itself is fused into the same pass (`compact_pred`): the mask is never
materialized in HBM.  `translate` points additionally emit the CSR
key→slot vector consumed by `pk_gather` (see `ir.Compact`).
"""
from __future__ import annotations

import numpy as np

from repro.core import ir
from repro.core.operators import fused as fu
from repro.core.expr import eval_expr
from repro.core.operators.base import (Binding, Frame, StageCtx, and_masks,
                                       frame_nrows, ones_mask)


def _apply_pred(f: Frame, pred, ctx: StageCtx) -> None:
    """Fall back from in-kernel evaluation: apply the intercepted Select's
    predicate to the already-staged frame the ordinary way."""
    f.mask = and_masks(ctx.xp, f.mask, eval_expr(pred, ctx.env(f)))


def stage(c: ir.Compact, ctx: StageCtx, defer: bool = False) -> Frame:
    be, xp = ctx.backend, ctx.xp
    s = ctx.settings
    use_k = s.use_pallas and be.name == "jax"
    # fused interception: under the kernel path, a Select whose predicate
    # is kernel-safe over a pure elementwise chain is absorbed into the
    # compaction kernel — stage its *child* and keep the predicate.  The
    # structural checks run BEFORE staging so the Select is never staged
    # twice; any post-staging surprise falls back to normal evaluation.
    pred = None
    if (use_k and isinstance(c.child, ir.Select)
            and fu.elementwise_chain(c.child.child)
            and fu.kernel_safe(c.child.pred)):
        pred = c.child.pred
        f = ctx.stage(c.child.child)
        if f.mask is not None or f.pending:
            _apply_pred(f, pred, ctx)
            pred = None
    else:
        f = ctx.stage(c.child)
    n = frame_nrows(f)
    cap = int(c.capacity)
    if cap <= 0:
        # measure-only point (the overflow twin): report the true valid
        # count, touch nothing — no gather, no truncation, so every
        # point's count is exact even below another point's overflow
        if pred is not None:
            _apply_pred(f, pred, ctx)
        count = xp.asarray(n, dtype=np.int32) if f.mask is None \
            else f.mask.astype(np.int32).sum()
        ctx.note_compact(c.point_id, count)
        return f
    if cap >= n:
        # nothing to win (also: the 8-row collection walk, where the frame
        # is a sample slice — schema and input registration are unaffected)
        if pred is not None:
            _apply_pred(f, pred, ctx)
        return f
    operands = None
    if pred is not None:
        operands = fu.collect_operands(f, [pred], [], ctx)
        if operands is None:           # a referenced column isn't 1-D numeric
            _apply_pred(f, pred, ctx)
            pred = None
    slot = None
    if pred is not None:
        from repro.kernels import ops as kops

        cols_d, scalars, pnames = operands
        res = kops.compact_pred(
            cols_d, scalars, fu.make_tile_fn(pred, pnames), int(cap),
            translate=c.translate, interpret=s.pallas_interpret)
        idx, count = res[0], res[1]
        if c.translate:
            slot = res[2]
    else:
        mask = f.mask if f.mask is not None else ones_mask(xp, n)
        if use_k:
            from repro.kernels import ops as kops

            res = kops.compact(mask, int(cap), translate=c.translate,
                               interpret=s.pallas_interpret)
            idx, count = res[0], res[1]
            if c.translate:
                slot = res[2]
        else:
            idx, count = be.compact(mask, cap)
            if c.translate:
                cs = xp.cumsum(mask.astype(np.int32))
                slot = xp.where(mask, cs - 1, np.int32(-1)).astype(np.int32)
    ctx.note_compact(c.point_id, count)
    cols = {name: Binding(be.take(b.arr, idx), b.kind, b.table, b.col)
            for name, b in f.cols.items()}
    newmask = xp.arange(cap, dtype=np.int32) < count
    return Frame(cols, newmask, f.pending, capacity=cap, slot_of=slot,
                 part=f.part)
