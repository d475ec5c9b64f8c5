"""The physical-operator layer: one module per operator, each a pure
function `stage(node, ctx, defer=False) -> Frame` over the shared
`StageCtx`.  `repro.core.compile` is the driver that runs this dispatch
twice (numpy collection walk, traced JAX walk) and wraps the result in a
`CompiledQuery`.

Each operator stages under `jax.named_scope("op.<operator>")`, so the
HLO it emits carries the operator in its `op_name` metadata, nested as
the plan nests: a device op's innermost `op.*` scope names the operator
that staged it."""
from __future__ import annotations

import jax

from repro.core import ir
from repro.core.operators import (agg, compact, exchange, join, limit,
                                  project, scan, select, sort)
from repro.core.operators.base import (Binding, Frame, FrameEnv, StageCtx,
                                       frame_nrows)

_DISPATCH = {
    ir.Scan: scan.stage,
    ir.Select: select.stage,
    ir.Project: project.stage,
    ir.Join: join.stage,
    ir.Agg: agg.stage,
    ir.Compact: compact.stage,
    ir.Exchange: exchange.stage,
    ir.Sort: sort.stage,
    ir.Limit: limit.stage,
}


def stage(node: ir.Plan, ctx: StageCtx, defer: bool = False) -> Frame:
    fn = _DISPATCH.get(type(node))
    if fn is None:
        raise TypeError(type(node))
    with jax.named_scope(f"op.{type(node).__name__.lower()}"):
        return fn(node, ctx, defer)


__all__ = ["Binding", "Frame", "FrameEnv", "StageCtx", "frame_nrows",
           "stage"]
