"""The seeded image, its OME-TIFF and the server's files.

Copied from chip_smoke.py (`seeded_image`, `write_fixture`), widened
to C channels and Z sections. The noise field is drawn once per seed
and every plane is that field rolled by a plane-specific offset over a
plane-specific smooth base, so a seed costs one draw, not one per
plane. Every run makes its image anew in its work directory: nothing
is kept from run to run, so set-up does not depend on which seeds a
checkout has seen. This module never touches JAX.
"""

import concurrent.futures
import json
import os

import numpy as np

REQUEST_TIMEOUT_S = 1100.0  # a cold shape compiles for minutes


def seeded_planes(seed: int, size_x: int, size_y: int, size_z: int,
                  size_c: int) -> np.ndarray:
    """(1, C, Z, Y, X) uint16, smooth base + gaussian noise (sigma 120,
    chip_smoke.py's field: compresses like microscopy, not like white
    noise). The same seed gives the same array."""
    rng = np.random.default_rng(seed)
    noise = rng.standard_normal((size_y, size_x), dtype=np.float32)
    noise *= 120.0
    xx = np.arange(size_x, dtype=np.float32)[None, :]
    yy = np.arange(size_y, dtype=np.float32)[:, None]
    data = np.empty((1, size_c, size_z, size_y, size_x), np.uint16)

    def fill(cz):
        c, z = cz
        k = c * size_z + z
        base = (
            2000.0 + 300.0 * c
            + 1500.0 * np.sin(xx / (97.0 + 7.0 * k) + 0.9 * k)
            + 1500.0 * np.cos(yy / (131.0 + 5.0 * k) + 0.4 * k)
        )
        plane = np.roll(noise, (977 * k, 1613 * k), axis=(0, 1))
        plane += base
        np.clip(plane, 0.0, 65535.0, out=plane)
        data[0, c, z] = plane  # the cast truncates, as astype does

    # numpy releases the GIL in these passes: three planes at a time
    with concurrent.futures.ThreadPoolExecutor(3) as pool:
        list(pool.map(fill, [(c, z) for c in range(size_c)
                             for z in range(size_z)]))
    return data


def cache_root(bench_dir: str) -> str:
    return os.path.join(bench_dir, ".cache")


def write_tiff(path: str, config: dict, data: np.ndarray) -> None:
    """The pyramidal OME-TIFF, through the program's own writer."""
    from omero_ms_pixel_buffer_tpu.io.ometiff import write_ome_tiff

    image = config["image"]
    tmp = path + ".part"
    write_ome_tiff(
        tmp, data, tile_size=(image["tile"], image["tile"]),
        compression=image["compression"],
        pyramid_levels=image["pyramid_levels"],
        bigtiff=bool(image.get("bigtiff", False)),
    )
    os.replace(tmp, path)


def write_server_files(workdir: str, config: dict, tiff: str,
                       port: int) -> tuple:
    """registry.json and config.yaml in the work directory."""
    registry = os.path.join(workdir, "registry.json")
    with open(registry, "w") as f:
        json.dump({"images": [{"id": 1, "path": tiff}]}, f)
    path = os.path.join(workdir, "config.yaml")
    with open(path, "w") as f:
        f.write(f"port: {port}\n")
        # the request deadline must outlast a cold compile
        f.write(f"event-bus-send-timeout: {int(REQUEST_TIMEOUT_S * 1000)}\n")
        f.write("\n".join(config["server_yaml"]) + "\n")
    return registry, path
