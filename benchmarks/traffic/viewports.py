"""The one general traffic generator: viewers looking at viewports.

A viewer draws a viewport of `viewport_tiles` = [cols, rows] adjacent
tiles (origin uniform on a `grid`-pixel lattice, whole viewport inside
the image), a z from `z_choices` and a c from `c_choices`, requests the
viewport's tiles in row order over its `connections_per_viewer`
keep-alive connections, then jumps to the next seeded viewport. One
tile per viewport and one connection per viewer is uniform random
tile traffic. Every parameter comes from the workload's data file; the
seed only orders the draws.
"""

import numpy as np


def viewer_stream(params: dict, image: dict, seed: int, viewer: int):
    """Endless iterator of one viewer's requests (dicts): the seed, the
    viewer's number and nothing else decide it."""
    rng = np.random.default_rng([seed, viewer, 0x7A11E5])
    tile, grid = params["tile"], params["grid"]
    cols, rows = params["viewport_tiles"]
    span_x = (image["size_x"] - cols * tile) // grid + 1
    span_y = (image["size_y"] - rows * tile) // grid + 1
    while True:
        x0 = int(rng.integers(span_x)) * grid
        y0 = int(rng.integers(span_y)) * grid
        z = int(rng.choice(params["z_choices"]))
        c = int(rng.choice(params["c_choices"]))
        for row in range(rows):
            for col in range(cols):
                request = {
                    "z": z, "c": c, "w": tile, "h": tile,
                    "x": x0 + col * tile, "y": y0 + row * tile,
                }
                request["url"] = params["path"].format(**request)
                yield request


def viewers(params: dict, image: dict, seed: int) -> list:
    """[(stream, connections)] for every viewer of the mix."""
    return [
        (viewer_stream(params, image, seed, v),
         params["connections_per_viewer"])
        for v in range(params["viewers"])
    ]
