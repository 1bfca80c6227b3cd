"""Device: the share of the traced slice in which a chip idled waiting
for a launch call already under way on the host: for each idle gap of
each `/device:TPU:<n>` plane, the time from the start of the
`ompb.queue.launch` of the program that ends the gap to the gap's end
(`_launches.py` has the match and the rest of the split: host late,
by the launching thread's stage; unmatched; edges), 100 × ÷ the slice,
the mean over the chips. Prints `idle_by_launch:`, per chip. None on a
trace without launch spans."""

from benchmarks.layer_metrics import _launches


def read(ctx):
    chips = _launches.of(ctx)
    if not chips:
        return None
    window_s = ctx["trace"]["window_s"]
    return sum(100.0 * c["in_launch_s"] / window_s for c in chips) / len(
        chips)
