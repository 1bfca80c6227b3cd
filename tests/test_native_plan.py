"""The host Huffman plan of the two-pass dynamic deflate as one native
call (`ompb_dynamic_plan_batch`, native ABI v5): its eight tables are
the Python plan's (`_lane_dynamic_plan`) bit for bit, so every stream,
PNG and ETag stays what it was; without an engine that carries it
`build_dynamic_tables` plans in Python and gives the same arrays; and
the device queue counts the real lanes each implementation planned.

No count vector gives a code-length tree of one code: the end-of-block
symbol always has a length, among at least 258 entries, so the run
coding uses two CL symbols or more. The dummy-code branch both plans
keep for it cannot be reached from counts, and no case here claims to."""

import numpy as np
import pytest
import zlib

from omero_ms_pixel_buffer_tpu.models import device_dispatch as dq
from omero_ms_pixel_buffer_tpu.ops import device_deflate as dd
from omero_ms_pixel_buffer_tpu.ops.png import filter_rows_np
from omero_ms_pixel_buffer_tpu.runtime import native

L = 4096
WAIT = 120


@pytest.fixture(scope="module")
def engine():
    eng = native.get_engine()
    assert eng is not None and eng.has_dynamic_plan
    return eng


def _geometric(seed, p, lanes=2):
    r = np.random.default_rng(seed)
    return np.minimum(r.geometric(p, (lanes, L)) - 1, 255).astype(np.uint8)


def _deep_tree():
    """Sixteen literals counted 1, 2, 3, 5, ... 1597 and no two alike
    side by side (so no match): with the end-of-block symbol's 1 the
    unlimited tree is a chain 16 deep, and the plan has to damp and
    rebuild to reach 15."""
    fib = [1, 2]
    while len(fib) < 16:
        fib.append(fib[-1] + fib[-2])
    left = {7 * i + 3: f for i, f in enumerate(fib)}
    out = []
    while any(left.values()):  # the commonest that differs from the last
        sym = max((s for s in left if left[s] and (not out or s != out[-1])),
                  key=lambda s: (left[s], -s))
        out.append(sym)
        left[sym] -= 1
    return np.asarray(out, np.uint8)[None]


def _noisy_uint16():
    """One 256x256 uint16 tile as the cells crop them (noise over a
    smooth base, a band of runs), Up-filtered: its PNG payload."""
    r = np.random.default_rng(2700)
    yy, xx = np.mgrid[0:256, 0:256]
    tile = 2000 + 3 * xx + 2 * yy + r.normal(0, 120, (256, 256))
    tile[60:110] = 4095
    rows = tile.clip(0, 65535).astype(">u2").view(np.uint8).reshape(256, 512)
    return filter_rows_np(rows, 2, "up").ravel()[None]


def _stats(payloads):
    counts, extras = (np.asarray(a) for a in dd._dyn_stats(payloads))
    return counts, extras


# case -> (payloads, counts, extras, real). Counts are the payloads'
# own pass-1 histogram, except where a case is about the counts alone.
def _case(name):
    if name == "skew low":
        payloads = np.random.default_rng(1).integers(
            0, 256, (2, L)).astype(np.uint8)
    elif name == "skew mid":
        payloads = _geometric(2, 0.03)
    elif name == "skew high":
        payloads = _geometric(3, 0.7)
    elif name == "single symbol":
        payloads = np.full((1, 2), 65, np.uint8)  # two literals, no match
    elif name == "no matches":
        payloads = (np.arange(2 * L) % 4 + 1).astype(np.uint8).reshape(2, L)
    elif name == "deep tree":
        payloads = _deep_tree()
    elif name == "pad lanes":
        payloads = _geometric(5, 0.1, lanes=4)
        counts, extras = _stats(payloads)
        return payloads, counts, extras, 2
    elif name == "noisy uint16":
        payloads = _noisy_uint16()
    elif name == "all-zero counts":
        # a plan from no tokens at all: the fixed code wins, and the
        # fixed tables encode any payload
        payloads = _geometric(6, 0.2)
        return payloads, np.zeros((2, 286), np.int32), np.zeros(2, np.int32), 2
    else:
        raise AssertionError(name)
    counts, extras = _stats(payloads)
    return payloads, counts, extras, len(payloads)


CASES = ["skew low", "skew mid", "skew high", "single symbol", "no matches",
         "deep tree", "pad lanes", "noisy uint16", "all-zero counts",
         "header over the cap"]


def _python_tables(monkeypatch, counts, extras, real):
    with monkeypatch.context() as m:
        m.setattr(dd, "_native_planner", lambda: None)
        assert dd.plan_impl() == "python"
        return dd.build_dynamic_tables(counts, extras, real=real)


def _fixed(tables, lane):
    """Lane ``lane`` of ``tables`` is the fixed code's prefill."""
    hdr_b, hdr_n, lit_b, lit_n, ml_b, ml_n, eob_b, eob_n = tables
    return (hdr_b[lane, 0] == 3 and hdr_n[lane, 0] == 3
            and not hdr_n[lane, 1:].any()
            and np.array_equal(lit_b[lane], dd._LIT_BITS)
            and np.array_equal(lit_n[lane], dd._LIT_NBITS)
            and np.array_equal(ml_b[lane], dd._MATCH_BITS)
            and np.array_equal(ml_n[lane], dd._MATCH_NBITS)
            and eob_b[lane] == 0 and eob_n[lane] == 7)


@pytest.mark.parametrize("case", CASES)
def test_the_native_plan_is_the_python_plan_bit_for_bit(
        engine, monkeypatch, case):
    if case == "header over the cap":
        # no count vector needs more than 310 tokens; a cap of 8 makes
        # every lane's header too long, and both plans keep fixed
        monkeypatch.setattr(dd, "_HDR_TOKENS", 8)
        payloads = _geometric(7, 0.05)
        counts, extras = _stats(payloads)
        real = 2
    else:
        payloads, counts, extras, real = _case(case)
    want = _python_tables(monkeypatch, counts, extras, real)
    assert dd.plan_impl() == "native"
    got = dd.build_dynamic_tables(counts, extras, real=real)
    assert len(got) == len(want) == 8
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)
    # what each case is there for, it does
    dynamic = [not _fixed(got, lane) for lane in range(len(counts))]
    if case in ("all-zero counts", "header over the cap"):
        assert not any(dynamic)
    elif case == "pad lanes":
        assert dynamic[:2] == [True, True] and dynamic[2:] == [False, False]
    elif case == "no matches":
        assert not counts[:, 257:].any() and all(dynamic)
    elif case == "deep tree":
        freq = counts[0].astype(np.int64)
        freq[256] = 1
        assert dd._build_lengths_np(freq, 286).max() == 16  # undamped
        assert got[3][0].max() <= 15 and dynamic[0]
    elif case == "single symbol":
        assert np.count_nonzero(counts[0]) == 1
    # and the streams they give inflate to the payloads
    streams, lengths = (np.asarray(a) for a in dd._zlib_dynamic(
        payloads, *got))
    for lane in range(real):
        assert zlib.decompress(
            streams[lane, : lengths[lane]].tobytes()) == payloads[lane].tobytes()


class _Abi4Lib:
    """The library as an ABI-4 build shows it: version 4, and no plan."""

    def __init__(self, lib):
        self._lib = lib
        self.ompb_version = lambda: 4

    def __getattr__(self, name):
        if name == "ompb_dynamic_plan_batch":
            raise AttributeError(name)
        return getattr(self._lib, name)


@pytest.fixture(params=["native", "no engine", "ABI 4"])
def planner(request, engine, monkeypatch):
    """The implementation ``build_dynamic_tables`` plans with, by what
    ``get_engine()`` hands it."""
    if request.param == "no engine":
        monkeypatch.setattr(native, "get_engine", lambda: None)
    elif request.param == "ABI 4":
        old = native.NativeEngine(_Abi4Lib(engine._lib))
        assert old.version == 4 and not old.has_dynamic_plan
        monkeypatch.setattr(native, "get_engine", lambda: old)
    return "native" if request.param == "native" else "python"


def test_every_engine_gives_the_same_tables(planner, monkeypatch):
    payloads = _geometric(8, 0.05, lanes=4)
    counts, extras = _stats(payloads)
    assert dd.plan_impl() == planner
    got = dd.build_dynamic_tables(counts, extras, real=3)
    want = _python_tables(monkeypatch, counts, extras, 3)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def _submit(disp, tiles, mode="dynamic"):
    b, n = tiles.shape[0], tiles.shape[1]
    return disp.submit(tiles, n, 1 + 2 * n, 2, "up", mode, list(range(b)),
                       [(n, n)] * b, 16, 0)


def _tiles(seed, b):
    return np.random.default_rng(seed).integers(
        0, 60000, (b, 16, 16)).astype(np.uint16)


def test_the_queue_counts_the_real_lanes_each_plan_served(planner):
    disp = dq.DeviceEncodeDispatcher({}, queue_depth=2)
    try:
        snap = disp.snapshot()
        assert snap["plan_lanes_native"] == snap["plan_lanes_python"] == 0
        # three lanes ride a group padded to four; the pad lane is no
        # planned lane, and a single-pass group plans nothing
        for mode, seed in (("dynamic", 1), ("rle", 2), ("dynamic", 3)):
            tiles = _tiles(seed, 3)
            out = _submit(disp, tiles, mode).result(timeout=WAIT)
            assert sorted(out) == [0, 1, 2]
        snap = disp.snapshot()
        other = "python" if planner == "native" else "native"
        assert snap[f"plan_lanes_{planner}"] == 6
        assert snap[f"plan_lanes_{other}"] == 0
    finally:
        disp.close()


@pytest.mark.resilience
def test_a_mesh_group_counts_its_lanes_and_the_width_warm_up_none(engine):
    """The mesh's dynamic method plans a group's real lanes once; the
    warm-up that a width change starts plans no lane (``real=0``) and
    counts none."""
    import jax

    from omero_ms_pixel_buffer_tpu.parallel.mesh import MeshManager
    from omero_ms_pixel_buffer_tpu.resilience import BOARD, INJECTOR
    from omero_ms_pixel_buffer_tpu.resilience.faultinject import first_n

    devices = jax.devices()
    mgr = MeshManager(devices=devices)
    mgr.mesh()
    disp = dq.DeviceEncodeDispatcher({}, mesh_manager=mgr)
    try:
        tiles = _tiles(4, 5)
        out = _submit(disp, tiles).result(timeout=WAIT)
        assert sorted(out) == list(range(5))
        assert disp.snapshot()["plan_lanes_native"] == 5
        INJECTOR.install(f"device.chip:{devices[3].id}",
                         first_n(1, RuntimeError("dead chip")))
        assert mgr.probe_device(devices[3]) is False
        disp._warm_thread.join(timeout=WAIT)
        assert any(w == len(devices) - 1 for (w, _) in disp._warmed)
        snap = disp.snapshot()
        assert (snap["plan_lanes_native"], snap["plan_lanes_python"]) == (5, 0)
    finally:
        disp.close()
        INJECTOR.clear()
        BOARD.reset()
        BOARD.configure(enabled=True)
