"""Native C++ recordio scanner (mxnet_tpu/src/recordio.cc via ctypes) —
byte-format parity with the pure-python reader and the bulk read lane.
Reference role: dmlc-core recordio + src/io/ C++ readers (N19/N26)."""

import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio, native


def _write_rec(tmp_path, n=32, indexed=True, seed=0):
    r = np.random.RandomState(seed)
    rec_path = os.path.join(str(tmp_path), "data.rec")
    idx_path = os.path.join(str(tmp_path), "data.idx")
    payloads = [r.bytes(int(r.randint(1, 200))) for _ in range(n)]
    if indexed:
        w = recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
        for i, p in enumerate(payloads):
            w.write_idx(i, p)
    else:
        w = recordio.MXRecordIO(rec_path, "w")
        for p in payloads:
            w.write(p)
    w.close()
    return rec_path, idx_path, payloads


def test_native_lib_builds():
    assert native.native_available(), \
        "g++ is in the image; the native recordio lane must build"


def test_native_reuse_is_keyed_on_source_hash_not_mtime(tmp_path,
                                                       monkeypatch):
    """A built library is reused while the stored digest of its source
    matches — whatever a copy did to mtimes — and rebuilt when it does
    not; everything lands in the one build directory."""
    monkeypatch.setenv("MXNET_NATIVE_CACHE", str(tmp_path))
    so = native._build("librecordio.so", native._SRC)
    assert os.path.dirname(so) == str(tmp_path)
    assert sorted(os.listdir(tmp_path)) == ["librecordio.so",
                                            "librecordio.so.sha256"]
    built = os.stat(so).st_mtime_ns
    os.utime(so, ns=(1, 1))                  # "older than the source"
    assert native._build("librecordio.so", native._SRC) == so
    assert os.stat(so).st_mtime_ns == 1      # reused, not rebuilt
    with open(so + ".sha256", "w") as f:
        f.write("digest of some other source\n")
    native._build("librecordio.so", native._SRC)
    assert os.stat(so).st_mtime_ns >= built  # rebuilt


def test_native_build_failure_warns_and_falls_back(tmp_path, monkeypatch):
    """No toolchain: a RuntimeWarning names the cause and the loader
    returns None (callers then take the pure-python path)."""
    monkeypatch.setenv("MXNET_NATIVE_CACHE", str(tmp_path))
    monkeypatch.setenv("PATH", str(tmp_path))          # no g++ here
    with pytest.warns(RuntimeWarning, match="librecordio.so unavailable"):
        assert native._load("librecordio.so", native._SRC,
                            native._bind) is None


def test_native_index_matches_python_scan(tmp_path):
    rec_path, _, payloads = _write_rec(tmp_path, indexed=False)
    scan = native.index_recordio(rec_path)
    assert scan is not None
    offs, lens = scan
    assert len(offs) == len(payloads)
    np.testing.assert_array_equal(lens,
                                  [len(p) for p in payloads])
    # python sequential read sees the same payloads at those lengths
    rd = recordio.MXRecordIO(rec_path, "r")
    for p in payloads:
        assert rd.read() == p
    rd.close()


def test_native_bulk_read_parity(tmp_path):
    rec_path, _, payloads = _write_rec(tmp_path, indexed=False, seed=3)
    offs, lens = native.index_recordio(rec_path)
    got = native.read_recordio_batch(rec_path, offs, lens)
    assert got == payloads


def test_indexed_read_batch_native_and_fallback(tmp_path):
    rec_path, idx_path, payloads = _write_rec(tmp_path, seed=5)
    rd = recordio.MXIndexedRecordIO(idx_path, rec_path, "r")
    picks = [3, 0, 17, 31, 8]
    got = rd.read_batch(picks)
    assert got == [payloads[i] for i in picks]
    # forced-fallback path returns identical bytes
    os.environ["MXNET_USE_NATIVE"] = "0"
    try:
        native._lib, native._tried = None, False
        got2 = rd.read_batch(picks)
        assert got2 == got
    finally:
        del os.environ["MXNET_USE_NATIVE"]
        native._lib, native._tried = None, False
    rd.close()


def test_native_rejects_garbage(tmp_path):
    bad = os.path.join(str(tmp_path), "bad.rec")
    with open(bad, "wb") as f:
        f.write(b"definitely not recordio framing")
    with pytest.raises(mx.MXNetError, match="framing"):
        native.index_recordio(bad)


def test_native_truncated_tail_rejected(tmp_path):
    """A record whose payload is cut off must fail the scan (not be indexed
    at its claimed length) — read_batch then falls back to python."""
    rec_path, _, payloads = _write_rec(tmp_path, indexed=False, seed=9)
    with open(rec_path, "r+b") as f:
        f.truncate(os.path.getsize(rec_path) - 3)
    with pytest.raises(mx.MXNetError):
        native.index_recordio(rec_path)


def test_read_batch_on_writer_raises(tmp_path):
    rec_path = os.path.join(str(tmp_path), "w.rec")
    idx_path = os.path.join(str(tmp_path), "w.idx")
    w = recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    w.write_idx(0, b"abc")
    with pytest.raises(mx.MXNetError, match="writing"):
        w.read_batch([0])
    w.close()


def test_image_record_iter_bulk_path(tmp_path):
    """ImageRecordIter over a real .rec: one native bulk read per batch,
    correct shapes/labels (reference iter_image_recordio_2.cc contract)."""
    import cv2
    rec_path = os.path.join(str(tmp_path), "img.rec")
    idx_path = os.path.join(str(tmp_path), "img.idx")
    w = recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    r = np.random.RandomState(0)
    n = 12
    for i in range(n):
        img = (r.rand(10, 10, 3) * 255).astype(np.uint8)
        ok, buf = cv2.imencode(".png", img)
        assert ok
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i % 3), i, 0), buf.tobytes()))
    w.close()

    it = mx.io.ImageRecordIter(path_imgrec=rec_path, path_imgidx=idx_path,
                               data_shape=(3, 8, 8), batch_size=4,
                               preprocess_threads=2)
    seen_labels = []
    batches = 0
    for batch in it:
        assert batch.data[0].shape == (4, 3, 8, 8)
        seen_labels.extend(batch.label[0].asnumpy().tolist())
        batches += 1
    assert batches == n // 4
    assert sorted(set(seen_labels)) == [0.0, 1.0, 2.0]


def test_image_record_iter_process_decoder(tmp_path):
    """decoder='processes' (multiprocess decode pool — the reference's
    decode-worker role without the GIL) yields the same deterministic
    batches as in-process decode (no augmentation => exact match)."""
    import cv2
    rec_path = os.path.join(str(tmp_path), "imgp.rec")
    idx_path = os.path.join(str(tmp_path), "imgp.idx")
    w = recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    r = np.random.RandomState(5)
    for i in range(8):
        img = (r.rand(12, 12, 3) * 255).astype(np.uint8)
        ok, buf = cv2.imencode(".png", img)
        assert ok
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0), buf.tobytes()))
    w.close()

    def collect(decoder, threads):
        it = mx.io.ImageRecordIter(
            path_imgrec=rec_path, path_imgidx=idx_path,
            data_shape=(3, 8, 8), batch_size=4, decoder=decoder,
            preprocess_threads=threads, ctx=mx.cpu())
        out = [(b.data[0].asnumpy(), b.label[0].asnumpy()) for b in it]
        it.close()
        return out

    ref = collect("threads", 1)
    got = collect("processes", 2)
    assert len(ref) == len(got) == 2
    for (rd, rl), (gd, gl) in zip(ref, got):
        np.testing.assert_allclose(gd, rd)
        np.testing.assert_allclose(gl, rl)


# -- r5: native fused JPEG decode (src/jpeg_decode.cc) ---------------------

def _jpeg_bytes(img_rgb, quality=95):
    import cv2
    ok, buf = cv2.imencode(".jpg", cv2.cvtColor(img_rgb, cv2.COLOR_RGB2BGR),
                           [cv2.IMWRITE_JPEG_QUALITY, quality])
    assert ok
    return buf.tobytes()


def test_jpeg_decode_parity_and_mirror():
    """Fused decode+crop+normalize matches the cv2 reference path within
    the documented IFAST tolerance (<= ~4/255), incl. mirror and offsets."""
    import cv2
    from mxnet_tpu import native
    if not native.jpeg_decode_available():
        pytest.skip("no native jpeg decoder on this host")
    yy, xx = np.mgrid[0:96, 0:96]
    img = np.stack([xx * 2, yy * 2, xx + yy], -1).astype(np.uint8)
    b = _jpeg_bytes(img)
    assert native.jpeg_dims(b) == (96, 96)
    full = cv2.cvtColor(cv2.imdecode(np.frombuffer(b, np.uint8),
                                     cv2.IMREAD_COLOR), cv2.COLOR_BGR2RGB)
    mean, std = (10.0, 20.0, 30.0), (50.0, 60.0, 70.0)
    for xy, mirror in (((0, 0), False), ((5, 9), False), ((5, 9), True)):
        out = native.jpeg_decode_crop_norm(b, (64, 64), crop_xy=xy,
                                           mirror=mirror, mean=mean,
                                           std=std)
        ref = full[xy[1]:xy[1] + 64, xy[0]:xy[0] + 64].astype(np.float32)
        if mirror:
            ref = ref[:, ::-1]
        ref = (ref - np.array(mean, np.float32)) / np.array(std, np.float32)
        diff = np.abs(ref.transpose(2, 0, 1) - out)
        # IFAST DCT + plain upsampling: <= ~4 raw units / min(std)
        assert diff.max() <= 5.0 / 50.0, (xy, mirror, diff.max())


def test_jpeg_decode_scaled_and_fallbacks():
    from mxnet_tpu import native
    if not native.jpeg_decode_available():
        pytest.skip("no native jpeg decoder on this host")
    img = np.random.RandomState(0).randint(0, 255, (512, 512, 3), np.uint8)
    b = _jpeg_bytes(img)
    # min_side <= 0: FULL decode (crop semantics demand original pixels)
    out = native.jpeg_decode_crop_norm(b, (96, 96), crop_xy=(400, 400))
    assert out is not None and out.shape == (3, 96, 96)
    # min_side > 0: scaled IDCT may shrink, still covering crop+min_side
    out = native.jpeg_decode_crop_norm(b, (224, 224), min_side=256)
    assert out is not None and out.shape == (3, 224, 224)
    # undersized image -> None (caller falls back to the resize path)
    small = _jpeg_bytes(np.zeros((32, 32, 3), np.uint8))
    assert native.jpeg_decode_crop_norm(small, (64, 64)) is None
    # non-JPEG payload -> None
    assert native.jpeg_decode_crop_norm(b"not a jpeg", (8, 8)) is None
    assert native.jpeg_dims(b"nope") is None
