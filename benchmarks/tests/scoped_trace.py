#!/usr/bin/env python3
"""Makes `scoped.xplane.pb`, the small trace the reader tests run on:

    chiprun --chips 1 -- python3 benchmarks/tests/scoped_trace.py chiprun_out/scoped

On the chip, three groups of two 16x16 uint16 tiles go through the
program's real device queue (`DeviceEncodeDispatcher`: two `dynamic`
groups and one `rle`), compiled first and then traced, so the file
holds the five kernel scopes in the operations' metadata, the
programs' optimized HLO, and the `ompb.queue.*` stages and waits of
three groups on the host plane (the committed file was recorded while
the slot wait was still annotated: it holds `ompb.queue.wait_slot`
events too, which the readers skip). No test runs this: it needs the
chip.
The process holds the chip itself; nothing else may run beside it.
"""

import glob
import os
import shutil
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out_dir: str) -> int:
    import jax

    from omero_ms_pixel_buffer_tpu.models.device_dispatch import (
        DeviceEncodeDispatcher,
    )

    print("device:", jax.devices()[0].platform, jax.devices()[0].device_kind)
    rng = np.random.default_rng(26)
    n = 16
    tiles = (rng.standard_normal((2, n, n)) * 120 + 2000).astype(np.uint16)
    disp = DeviceEncodeDispatcher({}, queue_depth=2)

    def groups():
        futures = [
            disp.submit(tiles, n, 1 + n * 2, 2, "up", mode, [0, 1],
                        [(n, n)] * 2, 16, 0)
            for mode in ("dynamic", "dynamic", "rle")
        ]
        return [f.result(timeout=900) for f in futures]

    try:
        groups()  # compiles
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        shutil.rmtree(out_dir, ignore_errors=True)
        jax.profiler.start_trace(out_dir, profiler_options=options)
        try:
            groups()
        finally:
            jax.profiler.stop_trace()
    finally:
        disp.close()
    (path,) = glob.glob(
        os.path.join(out_dir, "plugins", "profile", "*", "*.xplane.pb"))
    kept = os.path.join(os.path.dirname(out_dir.rstrip("/")) or ".",
                        "scoped.xplane.pb")
    shutil.copy(path, kept)
    print("wrote", kept, os.path.getsize(kept), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/scoped"))
