"""Names for the kernels of the device tile programs.

Every operation of the jitted tile programs (ops/device_deflate.py,
ops/png.py) sits under one of five scopes, entered where the work is
written, so a profiler trace of the chip says which kernel an
operation belongs to instead of ``%fusion.47``:

    ompb_filter   byteswap, PNG scanline filter, byte-row reshapes
    ompb_hist     pass-1 symbol counts of the dynamic encode
    ompb_tokens   run decomposition and per-position (bits, nbits)
    ompb_pack     the bit packer (second level: offsets, searchsorted
                  -- the count of tokens below each word edge --
                  and gather, so a trace says which step costs what)
    ompb_frame    adler32, zlib framing, the stored fallback

``kernel(scope)`` makes the function an inner ``jax.jit`` whose symbol
is the scope and runs its body under ``jax.named_scope(scope)``. The
named scope alone puts the name into the HLO metadata, which is what
the trace shows — but the persistent compile cache hashes the module
after ``strip-debuginfo``, so a program that differs from a cached one
only in scope names gets the cached executable back, without them. A
private function's symbol (``func.func private @ompb_pack``) survives
that pass: the key changes once, with the names, and not with source
line numbers. XLA inlines the calls before fusion.
"""

from __future__ import annotations

import functools

import jax

SCOPES = ("ompb_filter", "ompb_hist", "ompb_tokens", "ompb_pack", "ompb_frame")


def kernel(scope: str, static_argnames=()):
    """Decorator: the function as an inner jit named ``scope`` with its
    body under ``jax.named_scope(scope)``."""
    if scope not in SCOPES:
        raise ValueError(f"unknown kernel scope: {scope}")

    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(scope):
                return fn(*args, **kwargs)

        scoped.__name__ = scoped.__qualname__ = scope
        return jax.jit(scoped, static_argnames=static_argnames)  # ompb-lint: disable=jax-hotpath -- a decorator: runs once per kernel at import, the jit it returns IS the module-level cache

    return wrap
