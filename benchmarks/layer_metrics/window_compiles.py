"""Compile cache: entries that appeared during the window. Every
compile is persisted (runtime/jax_cache.py), so anything but 0 means
set-up missed a shape."""


def read(ctx):
    return float(ctx["after"]["cache_entries"]
                 - ctx["before"]["cache_entries"])
