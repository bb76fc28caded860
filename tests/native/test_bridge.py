"""Native staging bridge: build, ring semantics, packing oracle, feeder
end-to-end, and the pure-Python fallback path."""

import threading
import time

import numpy as np
import pytest

from sparkdl_tpu.native import _lib
from sparkdl_tpu.native.bridge import (
    DeviceFeeder,
    StagingRing,
    native_available,
    pack_rows,
    u8_to_f32,
)


def test_native_library_builds():
    assert _lib.available(), "g++ is in the image; the bridge must build"


def test_ring_fifo_and_wraparound():
    with StagingRing(slot_bytes=64, n_slots=2) as ring:
        seen = []
        for batch_no in range(5):  # > n_slots: exercises recycling
            w = ring.acquire_write(timeout_s=1.0)
            assert w is not None
            ring.slot_view(w)[:8] = batch_no
            ring.commit_write(w, n_rows=batch_no + 1, used_bytes=8)
            r = ring.acquire_read(timeout_s=1.0)
            assert r is not None
            assert ring.slot_rows(r) == batch_no + 1
            seen.append(int(ring.slot_view(r)[0]))
            ring.release_read(r)
        assert seen == [0, 1, 2, 3, 4]


def test_ring_blocking_and_close():
    ring = StagingRing(slot_bytes=16, n_slots=1)
    w = ring.acquire_write()
    ring.commit_write(w, 1, 4)
    # no free slot now: a write acquire must time out
    assert ring.acquire_write(timeout_s=0.05) is None
    # reader drains, then close -> next read returns None with closed=True
    r = ring.acquire_read(timeout_s=1.0)
    ring.release_read(r)
    ring.close()
    assert ring.acquire_read(timeout_s=1.0) is None
    assert ring.closed and ring.drained
    ring.destroy()


def test_timed_out_read_then_commit_and_close_keeps_the_last_batch():
    """The feed's end-of-stream race: a read times out, THEN the producer
    commits its final batch and closes. ``closed`` is already true with
    a batch still queued — a reader that broke on it dropped the batch
    (DeepImageFeaturizer then yielded fewer rows than it was fed).
    ``drained`` only turns true once a read finds the ring empty."""
    ring = StagingRing(slot_bytes=16, n_slots=2)
    assert ring.acquire_read(timeout_s=0.01) is None  # timed out
    w = ring.acquire_write()
    ring.commit_write(w, 1, 4)
    ring.close()
    assert ring.closed and not ring.drained
    r = ring.acquire_read(timeout_s=1.0)
    assert r == w
    ring.release_read(r)
    assert ring.acquire_read(timeout_s=1.0) is None and ring.drained
    ring.destroy()


def test_ring_cross_thread():
    ring = StagingRing(slot_bytes=1024, n_slots=3)
    n_batches, got = 50, []

    def producer():
        for i in range(n_batches):
            w = ring.acquire_write()
            view = ring.slot_view(w)
            view[:4] = np.frombuffer(np.int32(i).tobytes(), np.uint8)
            ring.commit_write(w, 1, 4)
        ring.close()

    t = threading.Thread(target=producer)
    t.start()
    while True:
        r = ring.acquire_read(timeout_s=2.0)
        if r is None:
            assert ring.drained
            break
        got.append(int(ring.slot_view(r)[:4].view(np.int32)[0]))
        ring.release_read(r)
    t.join()
    ring.destroy()
    assert got == list(range(n_batches))


def test_pack_rows_matches_numpy_stack():
    rng = np.random.default_rng(0)
    rows = [rng.integers(0, 255, 48, dtype=np.uint8) for _ in range(5)]
    packed = pack_rows(rows, bucket=8, row_stride=48)
    want = np.stack(rows + [rows[0]] * 3)
    np.testing.assert_array_equal(packed, want)


def test_pack_rows_zero_fills_short_rows():
    rows = [np.arange(10, dtype=np.uint8), np.arange(4, dtype=np.uint8)]
    packed = pack_rows(rows, row_stride=10)
    assert packed.shape == (2, 10)
    np.testing.assert_array_equal(packed[1, :4], np.arange(4))
    np.testing.assert_array_equal(packed[1, 4:], np.zeros(6, np.uint8))


def test_pack_rows_into_preallocated_out():
    rows = [np.full(8, i, np.uint8) for i in range(3)]
    out = np.zeros(4 * 8, np.uint8)
    view = pack_rows(rows, bucket=4, row_stride=8, out=out)
    assert view.base is out or view.base is not None
    np.testing.assert_array_equal(out.reshape(4, 8)[2], np.full(8, 2))
    np.testing.assert_array_equal(out.reshape(4, 8)[3], np.zeros(8))  # row 0 pad


def test_u8_to_f32():
    x = np.arange(256, dtype=np.uint8)
    got = u8_to_f32(x, scale=2.0 / 255.0, bias=-1.0)
    np.testing.assert_allclose(got, x.astype(np.float32) * 2 / 255 - 1, atol=1e-6)


def test_device_feeder_end_to_end():
    rng = np.random.default_rng(1)
    batches = [rng.standard_normal((4, 8)).astype(np.float32) for _ in range(7)]
    out = list(DeviceFeeder(iter(batches), n_slots=3))
    assert len(out) == 7
    for got, want in zip(out, batches):
        np.testing.assert_array_equal(np.asarray(got), want)


def test_device_feeder_ragged_leading_dim():
    batches = [np.ones((n, 4), np.float32) * n for n in (4, 2, 4, 1)]
    out = list(DeviceFeeder(iter(batches), max_batch_bytes=4 * 4 * 4))
    assert [a.shape[0] for a in out] == [4, 2, 4, 1]


def test_device_feeder_oversized_batch_raises():
    batches = [np.ones((2, 2), np.float32), np.ones((64, 64), np.float32)]
    with pytest.raises(ValueError, match="exceeds its slot segment"):
        list(DeviceFeeder(iter(batches)))


def test_device_feeder_python_fallback(monkeypatch):
    import sparkdl_tpu.native.bridge as bridge_mod

    monkeypatch.setattr(bridge_mod, "native_available", lambda: False)
    batches = [np.full((2, 3), i, np.float32) for i in range(4)]
    out = list(DeviceFeeder(iter(batches)))
    assert len(out) == 4
    np.testing.assert_array_equal(np.asarray(out[3]), np.full((2, 3), 3))


def test_native_assemble_matches_numpy_path():
    """runtime.batching._assemble: native packer and np.stack agree, and the
    result round-trips the dtype view (float32 image rows, > native
    threshold)."""
    from sparkdl_tpu.runtime import batching

    rng = np.random.default_rng(5)
    rows = [rng.standard_normal((96, 96, 3)).astype(np.float32)
            for _ in range(12)]
    assert rows[0].nbytes * 16 >= batching._NATIVE_PACK_MIN_BYTES
    got = batching._assemble(rows, bucket=16)
    want = np.concatenate([np.stack(rows), np.repeat(rows[0][None], 4, 0)])
    assert got.shape == (16, 96, 96, 3) and got.dtype == np.float32
    np.testing.assert_array_equal(got, want)


def test_feeder_overlap_smoke():
    """Transfer thread must keep the stream ordered under slow consumers."""
    batches = [np.full((2,), i, np.float32) for i in range(10)]
    got = []
    for arr in DeviceFeeder(iter(batches), n_slots=2):
        time.sleep(0.005)  # slow consumer
        got.append(float(np.asarray(arr)[0]))
    assert got == [float(i) for i in range(10)]


def test_pack_rows_pad_only_c_call_zero_fills():
    """Direct C-ABI pad-only call (n_rows=0, pad_rows>0): must zero-fill,
    not read the empty srcs array (the Python wrapper rejects empty rows,
    but the exported symbol has its own contract)."""
    import ctypes

    l = _lib.lib()
    if l is None:
        pytest.skip("native library unavailable")
    stride, pad = 16, 4
    dst = np.full(pad * stride, 0xAB, np.uint8)
    l.sdl_pack_rows(
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        None, None, 0, pad, 0, stride, 2,
    )
    assert not dst.any()


def test_device_feeder_single_slot_python_fallback_bounded(monkeypatch):
    """n_slots=1 on the fallback path must keep the prefetch queue bounded
    (maxsize>=1), not unbounded (maxsize=0)."""
    monkeypatch.setattr(
        "sparkdl_tpu.native.bridge.native_available", lambda: False
    )
    batches = [np.full((4,), i, np.float32) for i in range(6)]
    feeder = DeviceFeeder(iter(batches), n_slots=1)
    got = [np.asarray(b) for b in feeder]
    assert len(got) == 6
    np.testing.assert_array_equal(got[3], batches[3])


def test_a_library_built_from_other_source_is_never_loaded(
        tmp_path, monkeypatch):
    """The built file is named by a hash of its source and build command:
    a ``_build/`` left over from other source (copied trees keep no
    mtime relation) is simply not found, and the library rebuilds."""
    import shutil

    src = tmp_path / "csrc"
    src.mkdir()
    shutil.copy(_lib._BRIDGE._src, src / "sdl_bridge.cc")
    monkeypatch.setattr(_lib, "_HERE", str(tmp_path))
    monkeypatch.setattr(_lib, "_BUILD_DIR", str(tmp_path / "_build"))
    first = _lib.NativeLib("sdlbridge", "sdl_bridge.cc", _lib._declare_bridge)
    assert first.available() and first.built_here
    built = first.so_path()

    # same source, new process state: the cached build is reused
    again = _lib.NativeLib("sdlbridge", "sdl_bridge.cc", _lib._declare_bridge)
    assert again.available() and not again.built_here
    assert again.so_path() == built

    # the source changes under a stale _build/: different name, the old
    # file is not loaded, a new one is built
    with open(src / "sdl_bridge.cc", "a") as f:
        f.write("\n// edited\n")
    edited = _lib.NativeLib("sdlbridge", "sdl_bridge.cc",
                            _lib._declare_bridge)
    assert edited.so_path() != built
    assert edited.available() and edited.built_here
