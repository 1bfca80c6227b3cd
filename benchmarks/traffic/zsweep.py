"""Viewers stepping through the Z sections of a field of view.

A viewer draws a field of view (x, y), uniform on the `grid`-pixel
lattice with the whole tile inside the image, and a start section z0,
then asks for every (z, c): z = z0, z0 + 1, ... (mod `z_sections`)
through all the sections and, inside each, every c of `c_choices` in
order: `z_sections` x len(`c_choices`) requests at one (x, y, tile,
tile), handed in that order to its `connections_per_viewer` keep-alive
connections. Then it jumps to the next seeded field. `viewports.py`
cannot draw this: it holds (z, c) fixed inside a viewport. The seed
and the viewer's number decide everything.

The sweep is defined over a stack that is resident, and the harness
cannot see a program that ignores the deployment's plane budget: a
`config.yaml` with no `backend.plane-cache-mb` holds 20 of the 48
planes whatever the configuration says, and its serial writer takes
five minutes over the image (the parent of PR 28: 499 s a run, stopped
at the driver's 360). So this file, the first of the cell's that the
harness loads, refuses such a program before the image is drawn: exit
code 2, no result line.
"""

import dataclasses
import sys

import numpy as np


def program_has_plane_budget() -> bool:
    """Whether the program's `config.yaml` has the key that
    `fluor-zstack` sets (the import touches no JAX)."""
    from omero_ms_pixel_buffer_tpu.utils import config

    return any(f.name == "plane_cache_mb"
               for f in dataclasses.fields(config.BackendConfig))


if not program_has_plane_budget():
    print("this program's config.yaml has no backend.plane-cache-mb: it "
          "cannot hold the 48 planes of a Z stack resident, so the zsweep "
          "traffic has nothing to run against", file=sys.stderr)
    raise SystemExit(2)


def viewer_stream(params: dict, image: dict, seed: int, viewer: int):
    """Endless iterator of one viewer's requests (dicts)."""
    rng = np.random.default_rng([seed, viewer, 0x25EE9])
    tile, grid, sections = params["tile"], params["grid"], params["z_sections"]
    span_x = (image["size_x"] - tile) // grid + 1
    span_y = (image["size_y"] - tile) // grid + 1
    while True:
        x = int(rng.integers(span_x)) * grid
        y = int(rng.integers(span_y)) * grid
        z0 = int(rng.integers(sections))
        for step in range(sections):
            for c in params["c_choices"]:
                request = {
                    "z": (z0 + step) % sections, "c": int(c),
                    "w": tile, "h": tile, "x": x, "y": y,
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
