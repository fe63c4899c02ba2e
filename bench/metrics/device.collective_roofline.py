"""The all-to-alls' share of the interconnect's roofline: the least time the
chip-to-chip links (``ici_bits_per_s``) need for the bytes that leave each chip
((n - 1) / n of each all-to-all's operands, read from its HLO text by
``benchlib.collectives``), over the all-to-alls' device time, start to done."""

from benchlib.collectives import all_to_all_intervals, leaving_bytes


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    ici_bytes_per_s = float(run.peaks["ici_bits_per_s"]) / 8
    least = spent = 0.0
    for hlo, seconds in all_to_all_intervals(run.trace.ops):
        least += leaving_bytes(hlo, run.trace.n_devices) / ici_bytes_per_s
        spent += seconds
    return 100.0 * least / spent if spent and least else None
