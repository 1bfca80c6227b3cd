"""Pallas TPU kernels for the tile hot path."""

from .filter import filter_tiles, supports  # noqa: F401
