"""The real server with its timed path broken underneath: every batch
of crops that leaves the HBM plane cache has one sample of its first
lane altered (+1), where the answer is produced. Used only by
test_fault_real_server.py; started as the launcher is.

    python faulty_launcher.py <workdir> -- <server argv>
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "harness"))

import launcher  # noqa: E402  (benchmarks/harness/launcher.py)

from omero_ms_pixel_buffer_tpu.models import device_cache  # noqa: E402

_sound = device_cache.DevicePlaneCache.crop_batch


def _altered(self, plane, coords, bh, bw):
    batch = _sound(self, plane, coords, bh, bw)
    return batch.at[0, bh // 2, bw // 2].add(1)


device_cache.DevicePlaneCache.crop_batch = _altered

if __name__ == "__main__":
    launcher.main()
