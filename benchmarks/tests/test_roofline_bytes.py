"""The byte count behind `encode_roofline`, on a hand-made sample."""

import pytest

from benchmarks.harness.cell import load_plugin
from benchmarks.harness.peaks import peak

roofline = load_plugin("layer_metrics", "encode_roofline")


def _sample(t_done, body_len, status=200):
    return {"t_done": t_done, "body": b"x" * body_len, "status": status}


def test_work_bytes_counts_pixels_read_and_body_written():
    samples = [_sample(1.0, 1000), _sample(2.0, 3000)]
    assert roofline.work_bytes(samples, 524288) == 2 * 524288 + 4000


def test_share_uses_only_the_slice_and_the_published_bandwidth():
    ctx = {
        "workload": {"raw_bytes_per_tile": 500000},
        "device": {"kind": "TPU v5 lite"},
        "samples": [
            _sample(4.9, 10**6),            # before the slice
            _sample(5.5, 319000),           # inside
            _sample(6.0, 319000),           # inside (encoded on the host)
            _sample(6.5, 10**6, status=503),  # inside, not served
            _sample(8.1, 10**6),            # after
        ],
        "trace": {"busy_s": 0.001, "window_s": 3.0, "slice": (5.0, 8.0),
                  "device_lanes": 1},
    }
    # one device lane of 819000 bytes / 819e9 B/s = 1 us least time;
    # busy 1 ms -> 0.1 %: the tile the host encoded is no work of the chip
    assert roofline.read(ctx) == pytest.approx(0.1)
    ctx["trace"]["device_lanes"] = 2
    assert roofline.read(ctx) == pytest.approx(0.2)


def test_nothing_to_read_returns_nothing():
    ctx = {"workload": {"raw_bytes_per_tile": 1}, "samples": [],
           "device": {"kind": "TPU v5 lite"}, "trace": None}
    assert roofline.read(ctx) is None
    ctx["trace"] = {"busy_s": 0.5, "window_s": 1.0, "slice": (0.0, 1.0),
                    "device_lanes": 3}
    assert roofline.read(ctx) is None  # no tile completed in the slice
    ctx["samples"] = [_sample(0.5, 1000)]
    ctx["trace"]["device_lanes"] = 0
    assert roofline.read(ctx) is None  # the chip encoded no lane


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peak("TPU v9 imaginary", "hbm_bytes_per_s")
