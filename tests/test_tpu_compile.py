"""The Pallas kernels and an `opt-pallas` query program compiled for a
described TPU v5e (no chip attached): the TPU compiler refuses what the
interpreter accepts, and a program must fit one chip's 16 GB.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and every
pytest-xdist worker imports this file.  The persistent compilation cache
stays off around these compiles (their entries cannot be read back
without a chip)."""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import CompiledQuery, preset
from repro.kernels import ops
from repro.relational.queries import QUERIES

ROWS = 6_000_000          # lineitem at SF 1
HBM_BYTES = 16 * 2**30    # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _arg(one_chip, spec):
    """`dtype` -> a column of ROWS; `(dtype, shape)` -> that shape."""
    dtype, shape = spec if isinstance(spec, tuple) else (spec, (ROWS,))
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _pred(c, s):
    return (c["d"] >= s[0]) & (c["d"] < s[1]) & (c["q"] < s[2])


def _vals(c, s):
    return [c["p"] * c["q"], c["p"]]


# name -> (function of the kernel inputs, input specs for `_arg`)
KERNELS = {
    "compact": (lambda m: ops.compact(m, 1 << 20, interpret=False),
                [jnp.bool_]),
    "compact_translate": (
        lambda m: ops.compact(m, 1 << 20, translate=True, interpret=False),
        [jnp.bool_]),
    "compact_pred": (
        lambda d, q, lo, hi, qm: ops.compact_pred(
            {"d": d, "q": q}, [lo, hi, qm], _pred, 1 << 18,
            translate=True, interpret=False),
        [jnp.int32, jnp.float32]),
    "filter_agg_4096_groups": (
        lambda m, g, v: ops.filter_agg(m, g, v, 4096, interpret=False),
        [jnp.bool_, jnp.int32, (jnp.float32, (ROWS, 4))]),
    "selective_filter_agg": (
        lambda d, q, p, lo, hi, qm: ops.selective_filter_agg(
            {"d": d, "q": q, "p": p}, [lo, hi, qm], _pred, _vals, None, 2,
            1, interpret=False),
        [jnp.int32, jnp.float32, jnp.float32]),
    "selective_filter_agg_capacity": (
        lambda d, q, p, lo, hi, qm: ops.selective_filter_agg(
            {"d": d, "q": q, "p": p}, [lo, hi, qm], _pred, _vals,
            lambda c, s: c["d"] % 7, 2, 7, capacity=1 << 18,
            translate=True, interpret=False),
        [jnp.int32, jnp.float32, jnp.float32]),
    "gather_join_nation": (
        lambda fk, t: ops.gather_join(fk, t, interpret=False),
        [jnp.int32, (jnp.float32, (25, 3))]),
    "gather_join_640_keys": (
        lambda fk, t: ops.gather_join(fk, t, interpret=False),
        [jnp.int32, (jnp.float32, (640, 4))]),
    "masked_topk_10": (
        lambda v, m: ops.masked_topk(v, m, 10, interpret=False),
        [jnp.float32, jnp.bool_]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = KERNELS[name]
    args = [_arg(one_chip, spec) for spec in specs]
    if name.startswith(("compact_pred", "selective")):
        args += [jax.ShapeDtypeStruct((), t, sharding=one_chip)
                 for t in (jnp.int32, jnp.int32, jnp.float32)]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES


def test_opt_pallas_q6_compiles_for_v5e(db, one_chip):
    settings = dataclasses.replace(preset("opt-pallas"),
                                   pallas_interpret=False)
    cq = CompiledQuery(QUERIES["q6"](), db, settings)
    shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
              for k, v in cq.bind().items()}
    compiled = jax.jit(cq.fn).lower(shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel keeps its `name=` in the chip's program, on the custom
    # call the trace's kernel events are found by
    assert re.search(r'%pipeline[.\d]* = .*custom_call_target="tpu_custom_call"',
                     text)
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES
