"""Kernels: device milliseconds of the traced slice under the scope
`ompb_hist` (the pass-1 symbol counts of the dynamic encode),
per lane the device encoded in the slice. Self time, so a loop and its
body count once (see _scopes.py)."""

from benchmarks.layer_metrics import _scopes


def read(ctx):
    return _scopes.kernel_ms_per_lane(ctx, "hist")
