"""The collective ops of a reduced trace (`trace.Reduced.ops`).  A device
event's name is its HLO instruction, `%all-gather.3 = s32[400]{0}
all-gather(s32[100]{0} %x), ...`: a collective is known by its opcode,
or by its instruction's name where the compiler wrapped it in a generic
op (`%all-gather-start = (...) async-start(...)`, a fusion named after
it).  An async collective shows as two events, `-start` and `-done`; both
are device time, and its result is counted once, at the `-done`."""
from __future__ import annotations

import re

from chipbench.trace import _shape_bytes

KINDS = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute",
         "all-to-all")
_KIND = "(" + "|".join(KINDS) + r")(-start|-done)?"
_NAME = re.compile("%" + _KIND + r"\b")
_OPCODE = re.compile(r"\s" + _KIND + r"\(")
_ANY_OPCODE = re.compile(r"\s[a-z][\w-]*\(")


def _kind(name: str):
    """(kind, half) of a collective event, half '' when synchronous, else
    None."""
    lhs, _, rhs = name.partition(" = ")
    m = _NAME.match(lhs) or _OPCODE.search(" " + rhs)
    return None if m is None else (m.group(1), m.group(2) or "")


def collective_ops(ops: list) -> list:
    """The [start_ns, dur_ns, name] events of `ops` that are collectives."""
    return [e for e in ops if _kind(e[2]) is not None]


def received_bytes(name: str) -> int:
    """Bytes of a collective's result, which the chip holds afterwards: the
    shapes between ` = ` and the opcode.  0 for a `-start` half."""
    if _kind(name)[1] == "-start":
        return 0
    rhs = " " + name.partition(" = ")[2]
    op = _ANY_OPCODE.search(rhs)
    return _shape_bytes(rhs[:op.start()] if op else rhs)


def window_collectives(w):
    """The collective events of a window's trace, or None where there is
    nothing to read: no trace, no request, or a trace over several chips
    that holds no collective, which a sharded program always runs, so the
    names were not recognised."""
    if w.trace is None or not w.n_requests:
        return None
    ops = collective_ops(w.trace.ops)
    if not ops and w.trace.n_devices > 1:
        return None
    return ops
