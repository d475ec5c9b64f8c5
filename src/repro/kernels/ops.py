"""Query-engine entry points to the Pallas kernels.

`interpret` selects the Pallas execution mode: `None` (the default)
compiles the kernel when JAX's backend is a TPU and runs the (slow,
validation-only) Pallas interpreter elsewhere; an explicit bool forces
either mode (`Settings.pallas_interpret` threads the engine-level override
through).  `filter_agg_query` is the integration point used by
`repro.core.operators.agg` when `Settings.use_pallas` is on;
`selective_agg_query` is the fused selective pipeline's; `operators.compact`
calls `compact` / `compact_pred` directly.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.gather_join import gather_join
from repro.kernels.pipeline import (compact, compact_pred, compact_translate,
                                    filter_agg, resolve_interpret,
                                    selective_filter_agg)
from repro.kernels.topk import masked_topk

__all__ = ["filter_agg", "filter_agg_query", "gather_join", "masked_topk",
           "compact", "compact_translate", "compact_pred",
           "selective_filter_agg", "selective_agg_query",
           "resolve_interpret"]


def filter_agg_query(mask, gidx, value_cols, n_groups, *, interpret=None):
    """Aggregate a list of 1-D value columns (plus an implicit count column)
    in one fused kernel pass.  Returns (sums (G, A), counts (G,))."""
    ones = jnp.ones_like(mask, dtype=jnp.float32)
    vals = jnp.stack(list(value_cols) + [ones], axis=1).astype(jnp.float32)
    out = filter_agg(mask, gidx.astype(jnp.int32), vals, n_groups,
                     interpret=interpret)
    return out[:, :-1], out[:, -1]


def selective_agg_query(cols, scalars, pred_fn, value_fns, gidx_fn,
                        n_groups, *, interpret=None):
    """The q19-class pipeline: in-kernel predicate + grouped aggregation
    (an implicit count column is appended, mirroring `filter_agg_query`).
    Returns (sums (G, A), counts (G,), total_count)."""
    a = len(value_fns)

    def vals_fn(c, s):
        return [f(c, s) for f in value_fns] + [jnp.float32(1.0)]

    sums, total = selective_filter_agg(
        cols, scalars, pred_fn, vals_fn, gidx_fn, a + 1, n_groups,
        interpret=interpret)
    return sums[:, :-1], sums[:, -1], total
