"""Kernels: device milliseconds of the traced slice under the scope
`ompb_pack` (the bit packer (offsets, searchsorted, gather)),
per lane the device encoded in the slice. Self time, so a loop and its
body count once (see _scopes.py)."""

from benchmarks.layer_metrics import _scopes


def read(ctx):
    return _scopes.kernel_ms_per_lane(ctx, "pack")
