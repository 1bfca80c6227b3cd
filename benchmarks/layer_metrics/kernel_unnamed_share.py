"""Kernels: the share of the device's busy time in the traced slice
that lies under none of the five `ompb_*` scopes, after an operation
the compiler made has taken the scope of the operations it was made
for (see _scopes.py). It bounds what `kernel_ms_per_lane.*` leave out;
with no scope in the trace at all (the program has none, or a compile
cache gave back an executable from before the names) it reads
nothing, like they do."""

from benchmarks.layer_metrics import _scopes


def read(ctx):
    found = _scopes.of(ctx)
    if not found or not found["kernels"] or not found["kernels"]["busy_s"]:
        return None
    kernels = found["kernels"]
    return 100.0 * kernels["scopes"].get(None, 0.0) / kernels["busy_s"]
