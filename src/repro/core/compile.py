"""Whole-query staging driver: lowered plan -> one specialized JAX program.

This is the LegoBase code generator, reorganized into explicit layers:

  * the *physical operators* live in `repro.core.operators` — one module
    per operator, each a pure `stage(node, ctx) -> Frame` function over the
    shared `StageCtx`;
  * this module is the driver: it runs the operator dispatch twice — once
    eagerly on numpy with 8-row samples (the collection walk, which
    registers the exact input set: per-query specialized loading, §3.6.1)
    and once under `jax.jit` (the traced walk producing the fused XLA
    program) — and wraps the result in a `CompiledQuery`;
  * the *runtime layer* (`repro.core.plan_cache`, `repro.serve`) reuses
    CompiledQuery across executions.

Every input but the `param/<name>` scalars lives on the device once per
database (`Database.device_input`), shared by every compiled entry that
reads it, so a dispatch copies only the parameters host→device.

Query-specific literals (date-slice bounds, dictionary codes, key domains,
strides, pruned column sets) are baked in at staging time exactly as the
paper's generated C bakes them in.  `Param` nodes are the exception: a
numeric parameter becomes a *scalar input* of the staged program
(`param/<name>`), so `run(params=...)` re-executes the already-jitted XLA
callable under new bindings without re-staging or re-compiling — the
compile-once / bind-many amortization of Dashti et al.

Beyond bind-many: `run_many(bindings_list)` executes N bindings of the
same plan as ONE XLA dispatch.  The staged body is wrapped in `jax.vmap`
with `in_axes=None` for base columns / index structures (table data is
traced once and shared across the batch) and `in_axes=0` for the
`param/<name>` scalars, which become leading-axis vectors of shape (B,).
Batch sizes are padded up to power-of-two buckets (`bucket_size`) by
repeating the last binding and slicing the results, so batch-size churn
costs at most log2(max batch) retraces of the vmapped program.

With `Settings.fusion = False` an `optimization_barrier` is placed between
operator regions, reproducing the limited optimization scope of
template-expansion query compilers (paper Fig 2) for the ladder experiment.

Selection-vector compaction (passes/compaction.py) gives the staged program
a third output: a dict mapping each compaction point's id to its TRUE
valid count at runtime.  A count above the point's planned capacity means
the static buckets dropped rows, so `run`/`run_many` discard the outputs
and re-execute through the lazily compiled *uncompacted twin* of the same
logical plan — compaction is a performance bet whose worst case is
latency, never wrong results.  The counts themselves are accumulated per
entry (`observed_max`, underuse streaks) and harvested by `PlanCache`'s
feedback store, which re-plans capacities from measured headroom after
repeated overflows and shrinks them after sustained underuse.
"""
from __future__ import annotations

import collections
import copy
import dataclasses
import functools
import re
import threading
import time
from typing import Optional

import numpy as np

from repro import obs
from repro.core import ir
from repro.core.backend import RANK_OP, JaxBackend, NumpyBackend
from repro.core.expr import Param
from repro.core.mesh import AXIS, data_mesh, resolve_shards
from repro.core.operators import StageCtx, frame_nrows
from repro.core.passes.param_binding import plan_params
from repro.core.passes.pipeline import Settings, optimize
from repro.relational.loader import Database

_SAMPLE = 8

# module-level staging counter: incremented once per CompiledQuery
# construction.  The runtime layer's cache tests assert on this to prove
# that re-binding parameters performs no re-staging.  (QueryServer compiles
# on pool threads, so the increment takes a lock.)
STAGINGS = 0
_STAGINGS_LOCK = threading.Lock()


# the collectives a sharded program stages: Exchange gathers, in-operator
# combines and the count gather (`backend.JaxBackend`)
_COLLECTIVES = ("all_gather", "psum", "pmax", "pmin")
_RANK_SCOPE = re.compile(r"\brank\.(\w+)")


def _collective_bytes(jaxpr) -> int:
    """Bytes of every collective's result in a jaxpr and its sub-jaxprs:
    what each shard holds afterwards."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _COLLECTIVES:
            total += sum(v.aval.size * v.aval.dtype.itemsize
                         for v in eqn.outvars)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    total += _collective_bytes(sub)
    return total


def _rank_sites(jaxpr, route=None, sites=None) -> collections.Counter:
    """Rank sites in a jaxpr and its sub-jaxprs, by route: the equations
    that stage each site's rank (`backend.RANK_OP`) inside a
    `rank.<route>` scope.  A sub-jaxpr's name stacks are its own, so the
    route found on an equation holds for everything inside it."""
    sites = collections.Counter() if sites is None else sites
    for eqn in jaxpr.eqns:
        m = _RANK_SCOPE.search(str(eqn.source_info.name_stack))
        r = m.group(1) if m else route
        if r is not None and eqn.primitive.name == RANK_OP.get(r):
            sites[r] += 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _rank_sites(sub, r, sites)
    return sites


def bucket_size(n: int) -> int:
    """Power-of-two batch bucket: the (B,) param axis is padded up to this
    so the vmapped program retraces at most log2(max batch) times."""
    if n < 1:
        raise ValueError(f"batch must be non-empty (got {n})")
    return 1 << (n - 1).bit_length()


class CompiledQuery:
    """A staged, jitted query.  `params` supplies bindings for every
    runtime (numeric) Param left residual in the optimized plan; they are
    also the values used during the collection walk.  Compile-time params
    (string values, Limit.n) must have been substituted before
    construction — pass `bindings` to `optimize`, or go through
    `PlanCache`."""

    # tiering.Runnable surface: batched execution pads to pow2 buckets
    # (PlanCache.run_many charges the pad slots), and the tier name
    # defaults from the settings — the tiered cache overwrites it when it
    # builds this program as a specific ladder rung (e.g. 'interpret').
    pads_batches = True

    def __init__(self, plan: ir.Plan, db: Database, settings: Settings,
                 params: Optional[dict] = None,
                 est_params: Optional[dict] = None,
                 observed: Optional[dict] = None):
        import jax

        global STAGINGS
        with _STAGINGS_LOCK:
            STAGINGS += 1

        self.db = db
        self.settings = settings
        self.tier_name = "opt-pallas" if settings.use_pallas else "compiled"
        # compaction plants static-capacity points from cardinality
        # *estimates*; keep a pristine copy of the logical plan so an
        # estimate that undershoots at runtime (the overflow flag) can
        # compile the uncompacted twin lazily.  Hand-planted Compact nodes
        # can overflow even with the pass off, so the copy is gated on
        # either — only plans that provably stay uncompacted skip it.
        # A measure-only twin plants nothing that can overflow, so it
        # never needs a fallback of its own: skip the deepcopy.
        pristine = copy.deepcopy(plan) \
            if (settings.compaction and not settings.compact_measure_only) \
            or any(isinstance(n, ir.Compact) and n.capacity > 0
                   for n in ir.walk(plan)) else None
        t0 = time.perf_counter()
        # estimation inputs for the Compaction pass: initial-binding values
        # default to the construction-time params; `observed` carries the
        # feedback store's measured counts on a re-plan.  PlanCache passes
        # both explicitly so an entry's capacities always match the
        # memoized capacity signature in its cache key.
        self.plan = optimize(plan, db, settings,
                             est_params=est_params if est_params is not None
                             else (params or {}),
                             observed=observed)
        self.pass_time = time.perf_counter() - t0
        # one walk over the optimized plan: hand-planted Compact nodes get
        # stable `h<i>` ids (no pass-assigned candidate id), then the
        # points split into real compaction points (capacity > 0) and
        # measure-only probes (capacity 0 — the overflow twin's
        # observation points, which count but never truncate and can
        # never overflow)
        h, compacts = 0, []
        for n in ir.walk(self.plan):
            if isinstance(n, ir.Compact):
                if n.point_id is None:
                    n.point_id = f"h{h}"
                    h += 1
                compacts.append(n)
        real = [n for n in compacts if n.capacity > 0]
        self.compaction_points = len(real)
        self.capacities = tuple(n.capacity for n in real)
        self.point_caps = {n.point_id: int(n.capacity) for n in real}
        # translate points carry the key→slot contract whose overflow
        # drops whole-query results: PlanCache's shrink decay exempts them
        # so their capacities floor at the all-time measured max
        self.translate_points = {n.point_id for n in real if n.translate}
        self.measure_points = len(compacts) - len(real)
        self._pristine = pristine if self.compaction_points else None
        self._fallback: Optional["CompiledQuery"] = None
        self._fallback_lock = threading.Lock()
        self.n_overflows = 0      # executions (or batch slots) that fell back
        # adaptive-feedback observation state (harvested by PlanCache):
        # all-time max true count per point, and the current run of
        # consecutive all-points-underused executions with its window max
        self._obs_lock = threading.Lock()
        self.observed_max: dict[str, int] = {}
        # per-shard all-time max vectors (shape (n_shards,)) — the sharded
        # program reports every point's count per shard, and the skew
        # between slots is what the bench/feedback surfaces read
        self.observed_shard: dict[str, np.ndarray] = {}
        self.under_streak = 0     # consecutive executions, every point <cap/4
        self.streak_max: dict[str, int] = {}   # max counts within the streak
        self._cache_key: Optional[tuple] = None   # set by PlanCache

        # sharded execution: the Sharding pass resolved the same settings,
        # so the mesh shape here matches the per-shard capacities it
        # planted.  The staged fn is shard_map-wrapped below; partitioned
        # inputs are placed with a NamedSharding after the collection walk
        # so jit consumes them without host-side resharding.
        self.n_shards = resolve_shards(settings)
        self._mesh = data_mesh(self.n_shards) if self.n_shards > 1 else None

        spec = plan_params(self.plan)
        structural = sorted(n for n, i in spec.items() if i.structural)
        if structural:
            raise TypeError(
                f"compile-time parameters {structural} are unresolved; "
                "bind them via optimize(..., bindings=...) or PlanCache")
        self.param_spec: dict[str, str] = {n: i.dtype for n, i in spec.items()}
        self.param_defaults = {n: (params or {})[n] for n in self.param_spec
                               if n in (params or {})}
        missing = sorted(set(self.param_spec) - set(self.param_defaults))
        if missing:
            raise KeyError(f"no binding supplied for parameters {missing}")

        # 1. collection walk (numpy, 8-row samples): registers inputs and
        #    output schema; every static decision is exercised here.
        t0 = time.perf_counter()
        self.inputs: dict = {}

        def collect_input(key, make):
            if key not in self.inputs:
                self.inputs[key] = np.asarray(make())
            v = self.inputs[key]
            return v if v.ndim == 0 else v[:_SAMPLE]   # params are scalars

        sp = db.shard_plan(self.n_shards) if self.n_shards > 1 else None
        axis = AXIS if self._mesh is not None else None
        sampler = StageCtx(db, settings, NumpyBackend(), collect_input,
                           self.param_defaults, axis=axis,
                           n_shards=self.n_shards, shard_plan=sp)
        sample_frame = sampler.stage(self.plan)
        self.out_meta = [(name, b.kind, b.table, b.col)
                         for name, b in sample_frame.cols.items()]
        # input keys whose arrays are partitioned over the data axis
        # (registered by sharded Scans during the collection walk)
        self.sharded_keys = frozenset(sampler.sharded_keys)
        # a dead-but-declared param would desync the jit input tree:
        # register every declared param unconditionally.
        for name, dtype in self.param_spec.items():
            sampler.param(Param(name, dtype))
        # every input but the `param/<name>` scalars is the database's
        # device copy, shared by every entry that reads it.  Under the
        # mesh the unpartitioned ones are replicated on it: a
        # single-device array would be refused by the shard_map program.
        part = rep = None
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            part = NamedSharding(self._mesh, PartitionSpec(AXIS))
            rep = NamedSharding(self._mesh, PartitionSpec())
        for k, v in self.inputs.items():
            if not k.startswith("param/"):
                self.inputs[k] = db.device_input(
                    k, v, part if k in self.sharded_keys else rep)

        # 2. the staged program.  `body` is the staged walk shared by the
        #    scalar and the batched entry point; the entry points differ
        #    only in how the `param/<name>` inputs are shaped (scalar vs
        #    leading-axis vector split by vmap) and in which trace counter
        #    they bump.
        self.n_traces = 0         # scalar program traces (must stay 1)
        self.n_batch_traces = 0   # vmapped traces: one per new bucket size
        self.n_executions = 0     # XLA dispatches via run()/run_many()

        def body(inputs, batched=False):
            ctx = StageCtx(db, settings, JaxBackend(),
                           lambda key, make: inputs[key],
                           self.param_defaults, batched=batched,
                           axis=axis, n_shards=self.n_shards, shard_plan=sp)
            frame = ctx.stage(self.plan)
            out = {name: b.arr for name, b in frame.cols.items()}
            n = frame_nrows(frame)
            mask = frame.mask if frame.mask is not None \
                else ctx.xp.ones((n,), dtype=bool)
            # third program output: every compaction point's TRUE valid
            # count, keyed by point id (empty dict when the plan has
            # none).  count > capacity is the overflow signal; the counts
            # feed the plan cache's capacity feedback either way.  Under
            # the mesh each count is a shard-local scalar — all-gather to
            # a replicated (n_shards,) vector so the host sees per-shard
            # demand (overflow = max over slots).
            counts = dict(ctx.compact_counts)
            if self._mesh is not None:
                be = ctx.backend
                counts = {pid: be.all_gather(c, AXIS)
                          for pid, c in counts.items()}
            return out, mask, counts

        def fn(inputs):
            self.n_traces += 1   # host side effect: runs only while tracing
            return body(inputs)

        def fn_many(inputs):
            # inputs: base columns as in `fn`, `param/<name>` of shape (B,).
            # vmap splits the param axis, so `body` stages the identical
            # scalar program per slot while base columns are closed over
            # (broadcast, in_axes=None): table data enters the XLA program
            # once, shared across the whole batch.
            self.n_batch_traces += 1
            base = {k: v for k, v in inputs.items()
                    if not k.startswith("param/")}
            pvec = {k: v for k, v in inputs.items()
                    if k.startswith("param/")}
            return jax.vmap(
                lambda p: body({**base, **p}, batched=True))(pvec)

        def shard_wrap(inner):
            # the staged walk runs per shard under shard_map: partitioned
            # inputs split along the data axis, everything else (params
            # included) replicated.  Every output is replicated — the plan
            # ends in combined aggregates or above a gather Exchange, and
            # the counts are all-gathered in `body` — so out_specs is P(),
            # a replication the checker cannot always infer (check_vma off).
            # The in_specs dict is built per call because `bind` adds
            # param/<name> keys the collection-time input set lacks.
            from jax.sharding import PartitionSpec

            def call(inputs):
                specs = {k: (PartitionSpec(AXIS) if k in self.sharded_keys
                             else PartitionSpec())
                         for k in inputs}
                return jax.shard_map(inner, mesh=self._mesh,
                                     in_specs=(specs,),
                                     out_specs=PartitionSpec(),
                                     check_vma=False)(inputs)
            return call

        self.fn = fn if self._mesh is None else shard_wrap(fn)
        self._jitted = jax.jit(self.fn)
        self._jitted_many = jax.jit(
            fn_many if self._mesh is None else shard_wrap(fn_many))
        self.stage_time = time.perf_counter() - t0
        self._compile_time: Optional[float] = None

    @functools.cached_property
    def exchange_bytes(self) -> int:
        """Bytes each shard receives per dispatch of the scalar program,
        from its staged shapes (a trace, shared with the dispatch's own):
        the results of its Exchange gathers, combines and count gather.
        0 unsharded, where nothing moves."""
        if self._mesh is None:
            return 0
        return _collective_bytes(self._jitted.trace(self.bind()).jaxpr.jaxpr)

    @functools.cached_property
    def rank_routes(self) -> dict:
        """Rank sites of the scalar program by route (`JaxBackend.compact`
        and `.searchsorted`, each staged under `rank.<route>`), e.g.
        {'scatter': 1, 'search': 2}: from its staged jaxpr (a trace,
        shared with the dispatch's own)."""
        return dict(_rank_sites(self._jitted.trace(self.bind()).jaxpr.jaxpr))

    # -- explicit compile (for the Fig-22 experiment) -------------------------
    def compile(self):
        import jax

        t0 = time.perf_counter()
        lowered = jax.jit(self.fn).lower(self.inputs)
        self.lower_time = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        self._compile_time = time.perf_counter() - t0
        self.lowered = lowered
        self.compiled = compiled
        return compiled

    # -- parameter re-binding --------------------------------------------------
    def bind(self, params: Optional[dict] = None) -> dict:
        """Input dict for one execution: base columns + index structures
        (device arrays shared across bindings and entries) and the
        per-execution parameter scalars (host numpy).

        `params=None` executes under the construction-time bindings; a
        non-None dict must name *every* runtime parameter — a partial dict
        would silently mix bindings from two requests."""
        merged = self._check_bindings(params)
        if not self.param_spec:
            return self.inputs
        inputs = dict(self.inputs)
        for name, dtype in self.param_spec.items():
            inputs[f"param/{name}"] = np.asarray(merged[name], dtype=dtype)
        return inputs

    def _check_bindings(self, params: Optional[dict]) -> dict:
        if params is None:
            return self.param_defaults
        unknown = sorted(set(params) - set(self.param_spec))
        if unknown:
            raise KeyError(f"unknown parameters {unknown}; this plan "
                           f"takes {sorted(self.param_spec)}")
        missing = sorted(set(self.param_spec) - set(params))
        if missing:
            raise KeyError(f"no binding supplied for parameters "
                           f"{missing}")
        return params

    def bind_many(self, bindings_list) -> dict:
        """Input dict for one *batched* execution: base columns unchanged,
        `param/<name>` stacked to a (bucket,) leading-axis vector — the
        batch padded to `bucket_size(B)` by repeating the last binding
        (callers slice the results back to B rows).  A None entry stands
        for the construction-time bindings, like `run(params=None)`."""
        merged = [self._check_bindings(b) for b in bindings_list]
        pad = bucket_size(len(merged)) - len(merged)
        merged = merged + [merged[-1]] * pad
        inputs = dict(self.inputs)
        for name, dtype in self.param_spec.items():
            inputs[f"param/{name}"] = np.stack(
                [np.asarray(b[name], dtype=dtype) for b in merged])
        return inputs

    def _fallback_query(self) -> "CompiledQuery":
        """The uncompacted twin: same logical plan, no truncating points.
        Compiled lazily on the first overflow, at most once.  With the
        pass enabled it runs in *measure-only* mode: every candidate site
        gets a capacity-0 probe reporting its TRUE valid count, so one
        fallback execution hands the feedback store the exact demand at
        every site (counts from the compacted program are truncated below
        an overflowed point, and re-planning from truncated counts would
        converge one layer per k overflows instead of in one step)."""
        from repro.core.passes.compaction import strip_compaction

        with self._fallback_lock:
            if self._fallback is None:
                # hand-planted Compact nodes survive pass-disabling: strip
                # them too, or the twin would overflow all over again
                self._fallback = CompiledQuery(
                    strip_compaction(self._pristine), self.db,
                    dataclasses.replace(self.settings,
                                        compact_measure_only=True),
                    params=self.param_defaults)
                self._pristine = None   # handed over (passes mutated it)
            return self._fallback

    def _merge_twin_observations(self, twin: "CompiledQuery") -> None:
        """Fold the twin's measured true counts into this entry's
        observation state, where PlanCache's feedback step harvests
        them.  Max-merge: idempotent across repeated fallbacks."""
        with twin._obs_lock:
            obs = dict(twin.observed_max)
        with self._obs_lock:
            for pid, c in obs.items():
                if c > self.observed_max.get(pid, -1):
                    self.observed_max[pid] = c

    def _observe_shards(self, vecs: dict[str, np.ndarray]) -> None:
        """Elementwise-max merge of per-shard count vectors (shape
        (n_shards,)) into the all-time per-shard state."""
        with self._obs_lock:
            for pid, v in vecs.items():
                old = self.observed_shard.get(pid)
                self.observed_shard[pid] = \
                    v.copy() if old is None else np.maximum(old, v)

    def _observe(self, slot_counts: list[dict]) -> None:
        """Feedback accounting for a list of per-execution (or per-real-
        batch-slot) true-count dicts: all-time max per point, plus the
        consecutive-underuse streak and its window max (the shrink
        signal decays — a historical spike must not pin capacity up)."""
        with self._obs_lock:
            for counts in slot_counts:
                oflow = False
                under = any(pid in self.point_caps for pid in counts)
                for pid, c in counts.items():
                    if c > self.observed_max.get(pid, -1):
                        self.observed_max[pid] = c
                    cap = self.point_caps.get(pid)
                    if cap is None:     # measure-only probe: count only
                        continue
                    if c > cap:
                        oflow = True
                    if 4 * c >= cap:
                        under = False
                if oflow or not under:
                    self.under_streak = 0
                    self.streak_max = {}
                else:
                    self.under_streak += 1
                    for pid, c in counts.items():
                        if c > self.streak_max.get(pid, -1):
                            self.streak_max[pid] = c

    def run(self, params: Optional[dict] = None) -> dict[str, np.ndarray]:
        import jax

        self.n_executions += 1
        with obs.span("query.bind"):
            inputs = self.bind(params)
        with obs.span("query.dispatch"):
            out, mask, counts = self._jitted(inputs)
        with obs.span("query.fetch"):
            # sharded programs report an (n_shards,) vector per point;
            # overflow and the scalar feedback both key off the worst shard
            vecs = {pid: np.atleast_1d(np.asarray(c)).reshape(-1)
                    for pid, c in counts.items()}
            out = jax.tree.map(np.asarray, out)
            mask = np.asarray(mask)
        if self.compaction_points or self.measure_points:
            with obs.span("query.feedback"):
                counts = {pid: int(v.max()) for pid, v in vecs.items()}
                self._observe([counts])
                if self.n_shards > 1:
                    self._observe_shards(vecs)
                overflow = any(c > self.point_caps[pid]
                               for pid, c in counts.items()
                               if pid in self.point_caps)
            if overflow:
                # a capacity bucket overflowed: the compacted frames
                # dropped rows, so the outputs are unusable — re-execute
                # uncompacted; the twin's measure probes report every
                # site's TRUE count, folded back for the feedback store
                self.n_overflows += 1
                with obs.span("query.fallback"):
                    twin = self._fallback_query()
                    res = twin.run(params)
                    self._merge_twin_observations(twin)
                return res
        with obs.span("query.decode"):
            return self._decode(out, mask)

    def run_many(self, bindings_list) -> list[dict[str, np.ndarray]]:
        """Execute N bindings as ONE XLA dispatch (the vmapped program).

        Returns one decoded result dict per binding, positionally matching
        `bindings_list`; each is identical to `run(bindings_list[i])`.
        A plan with no runtime params degenerates to a single scalar
        execution whose result is replicated."""
        bindings_list = list(bindings_list)
        if not bindings_list:
            return []
        if not self.param_spec:
            for b in bindings_list:
                self._check_bindings(b)
            res = self.run()
            # independent array copies per slot, matching N run() calls
            # (callers may mutate their result in place)
            return [{k: np.copy(v) for k, v in res.items()}
                    for _ in bindings_list]
        import jax

        self.n_executions += 1
        with obs.span("query.bind"):
            inputs = self.bind_many(bindings_list)
        with obs.span("query.dispatch"):
            out, mask, counts = self._jitted_many(inputs)
        with obs.span("query.fetch"):
            out = jax.tree.map(np.asarray, out)
            mask = np.asarray(mask)
            counts = {pid: np.asarray(c) for pid, c in counts.items()}
        n_real = len(bindings_list)
        bad: list[int] = []
        if self.compaction_points or self.measure_points:
            # the bucket's pad slots (indices >= n_real, repeats of the
            # last binding) are masked out of overflow accounting, the
            # feedback observations, and the fallback re-runs: rows
            # nobody asked for must not trigger re-planning or wasted
            # uncompacted-twin executions
            # per-point shapes: (B,) unsharded, (B, n_shards) sharded —
            # np.max over a slot's entry covers both
            with obs.span("query.feedback"):
                slot_counts = [{pid: int(np.max(v[i]))
                                for pid, v in counts.items()}
                               for i in range(n_real)]
                self._observe(slot_counts)
                if self.n_shards > 1 and counts:
                    self._observe_shards(
                        {pid: np.atleast_1d(np.max(v[:n_real], axis=0))
                         for pid, v in counts.items()})
                bad = [i for i, sc in enumerate(slot_counts)
                       if any(c > self.point_caps[pid]
                              for pid, c in sc.items()
                              if pid in self.point_caps)]
        bad_set = set(bad)
        with obs.span("query.decode"):
            results = [None if i in bad_set
                       else self._decode({k: v[i] for k, v in out.items()},
                                         mask[i])
                       for i in range(n_real)]
        if bad:
            # per-slot overflow: only the overflowing bindings re-execute
            # through the uncompacted twin (itself one vmapped dispatch)
            self.n_overflows += len(bad)
            with obs.span("query.fallback"):
                twin = self._fallback_query()
                redo = twin.run_many([bindings_list[i] for i in bad])
                self._merge_twin_observations(twin)
            for i, r in zip(bad, redo):
                results[i] = r
        return results

    def input_nbytes(self) -> int:
        return int(sum(v.nbytes for v in self.inputs.values()))

    def _decode(self, out: dict[str, np.ndarray], mask: np.ndarray
                ) -> dict[str, np.ndarray]:
        return _decode_frame(out, mask, self.out_meta)


def _decode_frame(out, mask, out_meta) -> dict[str, np.ndarray]:
        res = {}
        for name, kind, table, colname in out_meta:
            v = out[name][mask]
            if kind == "codes":
                res[name] = table.vocabs[colname][np.clip(v, 0, None)].astype(str)
            elif kind == "chars":
                w = v.shape[1]
                b = np.ascontiguousarray(v).view(f"S{w}")[:, 0]
                res[name] = np.char.decode(
                    np.char.rstrip(b, b"\x00"), "ascii").astype(str)
            elif kind == "words":
                vocab = table.word_vocabs[colname]
                res[name] = np.array(
                    [" ".join(str(vocab[c]) for c in row if c >= 0)
                     for row in v])
            elif kind == "wordchars":
                w = v.shape[1]
                b = np.ascontiguousarray(v).view(f"S{w}")[:, 0]
                res[name] = np.char.decode(
                    np.char.rstrip(b, b"\x00"), "ascii").astype(str)
            else:
                res[name] = v
        return res


class CompiledQueryBatch:
    """Beyond-paper: cross-QUERY compilation.

    The paper's scope stops at one query; staging a *batch* of plans into a
    single XLA program lets the backend share work across queries — common
    base-column loads, shared dictionary inputs, identical scan+filter
    subplans (Q1/Q6 both stream lineitem) are CSE'd by XLA, and one fused
    executable amortizes dispatch.  `run()` returns per-query results
    identical to individual `CompiledQuery.run()`.
    """

    def __init__(self, plans, db: Database, settings: Settings):
        import jax

        if resolve_shards(settings) != 1:
            # each member would need its own shard_map scope and its own
            # partitioned input aliases; cross-query CSE across shard_map
            # boundaries buys nothing, so the combination is rejected
            # rather than half-supported
            raise NotImplementedError(
                "CompiledQueryBatch does not compose with sharded "
                "execution (Settings.shards != 1)")
        self.queries = [CompiledQuery(p, db, settings) for p in plans]
        self.inputs: dict = {}
        for q in self.queries:
            self.inputs.update(q.inputs)
        fns = [q.fn for q in self.queries]

        def batch_fn(inputs):
            return tuple(fn(inputs) for fn in fns)

        self.fn = batch_fn
        self._jitted = jax.jit(batch_fn)

    def run(self) -> list[dict[str, np.ndarray]]:
        import jax

        outs = self._jitted(self.inputs)
        results = []
        for q, (out, mask, counts) in zip(self.queries, outs):
            if q.compaction_points or q.measure_points:
                counts = {pid: int(np.asarray(c))
                          for pid, c in counts.items()}
                q._observe([counts])
                if any(c > q.point_caps[pid] for pid, c in counts.items()
                       if pid in q.point_caps):
                    # rare: that query's capacity overflowed — go straight
                    # to its uncompacted twin (q.run() would re-execute
                    # the compacted program only to watch it overflow
                    # again)
                    q.n_overflows += 1
                    twin = q._fallback_query()
                    results.append(twin.run())
                    q._merge_twin_observations(twin)
                    continue
            out = jax.tree.map(np.asarray, out)
            results.append(_decode_frame(out, np.asarray(mask), q.out_meta))
        return results

    def input_nbytes(self) -> int:
        return int(sum(v.nbytes for v in self.inputs.values()))
