"""The bytes an all-to-all moves between chips, read from its trace event.

A device op event of the trace is named by its HLO text, e.g.
``%all-to-all.3 = s32[2,4,64,3]{3,2,1,0:T(8,128)} all-to-all(s32[2,4,64,3]{...}
%fusion.1), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={1}``. Each
chip sends the operands whole, minus the part addressed to itself: of ``n``
equal parts, ``n - 1`` leave the chip. An all-to-all that the compiler splits
into ``all-to-all-start`` and ``all-to-all-done`` (or an ``async-start`` /
``async-done`` pair around one) is timed from the start to its done.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

#: bytes per element of the HLO element types.
ELEMENT_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
                 "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_ARRAY = re.compile(r"\b(" + "|".join(ELEMENT_BYTES) + r")\[([\d,]*)\]")
_OPCODE = re.compile(r" (all-to-all(?:-start|-done)?|async-start|async-done)\(")
_GROUPS = re.compile(r"replica_groups=\{\{([\d,]*)\}")


def all_to_all_kind(hlo: str) -> Optional[str]:
    """``"sync"``, ``"start"`` or ``"done"`` for an all-to-all event, else None."""
    m = _OPCODE.search(hlo)
    if m is None:
        return None
    op = m.group(1)
    if op.startswith("async-") and "all-to-all" not in hlo:
        return None
    if op.endswith("-start"):
        return "start"
    if op.endswith("-done"):
        return "done"
    return "sync"


def _operands(hlo: str) -> str:
    """The operand list of the op: the text inside the opcode's parentheses."""
    m = _OPCODE.search(hlo)
    depth, i = 1, m.end()
    for j in range(i, len(hlo)):
        depth += {"(": 1, ")": -1}.get(hlo[j], 0)
        if depth == 0:
            return hlo[i:j]
    return hlo[i:]


def operand_bytes(hlo: str) -> int:
    """Bytes of the arrays an all-to-all (or its start) hands to the exchange."""
    total = 0
    for dtype, dims in _ARRAY.findall(_operands(hlo)):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * ELEMENT_BYTES[dtype]
    return total


def group_size(hlo: str, default: int) -> int:
    """Chips in one all-to-all group, from ``replica_groups``; ``default`` when
    the text does not say."""
    m = _GROUPS.search(hlo)
    return len([x for x in m.group(1).split(",") if x]) if m else default


def leaving_bytes(hlo: str, default_group: int) -> float:
    """Bytes that leave one chip in this all-to-all: (n - 1) / n of its operands."""
    n = group_size(hlo, default_group)
    return operand_bytes(hlo) * (n - 1) / n if n > 1 else 0.0


def all_to_all_intervals(ops: Iterable) -> List[Tuple[str, float]]:
    """(HLO text, seconds) of each all-to-all on each device: a sync op's own
    duration, a start's from its start to the end of the next done on the same
    device. ``ops`` are the trace's ``OpEvent``s (name, start_ns, dur_ns, device)."""
    out: List[Tuple[str, float]] = []
    open_starts: Dict[int, List] = {}
    for ev in sorted(ops, key=lambda e: (e.device, e.start_ns)):
        kind = all_to_all_kind(ev.name)
        if kind == "sync":
            out.append((ev.name, ev.dur_ns / 1e9))
        elif kind == "start":
            open_starts.setdefault(ev.device, []).append(ev)
        elif kind == "done" and open_starts.get(ev.device):
            start = open_starts[ev.device].pop(0)
            out.append((start.name, (ev.start_ns + ev.dur_ns - start.start_ns) / 1e9))
    return out
