"""The probe kernels' share of their roofline: the least time the chip's memory
needs for the bytes the operations need (``benchlib.work``: sorted keys in,
match ranges out, pairs out), over the kernels' device time. The shapes are read
from each kernel event's HLO text: ``merge_join_counts`` takes (a keys, b keys),
``merge_join_pairs`` returns (a_idx, b_idx, scratch) of the pair capacity."""

import re

from benchlib.work import least_time_s, pair_expansion_bytes, probe_bytes

SHAPE = re.compile(r"s32\[(\d+)\]")


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    least = spent = 0.0
    for ev in run.trace.ops:
        outputs, _, operands = ev.name.partition(" custom-call(")
        if "merge_join_counts" in outputs:
            a, b = (int(x) for x in SHAPE.findall(operands)[:2])
            least += least_time_s(probe_bytes(a, b), run.peaks)
        elif "merge_join_pairs" in outputs:
            least += least_time_s(pair_expansion_bytes(int(SHAPE.findall(outputs)[0])),
                                  run.peaks)
        else:
            continue
        spent += ev.dur_ns / 1e9
    return 100.0 * least / spent if spent else None
