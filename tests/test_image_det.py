"""Detection data-pipeline tests (reference
python/mxnet/image/detection.py + src/io ImageDetRecordIter + im2rec
--pack-label)."""

import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import image, recordio

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _packed_label(boxes):
    """[A=4, B=5, 0, 0, (cls x0 y0 x1 y1)*]"""
    flat = [4, 5, 0, 0]
    for b in boxes:
        flat.extend(b)
    return np.asarray(flat, np.float32)


def _write_det_rec(tmp_path, n=10, size=40, seed=0):
    import cv2
    prefix = os.path.join(str(tmp_path), "det")
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    rng = np.random.RandomState(seed)
    truths = []
    for i in range(n):
        img = np.zeros((size, size, 3), np.uint8)
        w = rng.randint(10, 18)
        x0 = rng.randint(0, size - w)
        y0 = rng.randint(0, size - w)
        img[y0:y0 + w, x0:x0 + w] = (255, 128, 0)
        box = [float(i % 3), x0 / size, y0 / size,
               (x0 + w) / size, (y0 + w) / size]
        truths.append(box)
        ok, buf = cv2.imencode(".png", img)
        assert ok
        rec.write_idx(i, recordio.pack(
            recordio.IRHeader(0, _packed_label([box]), i, 0),
            buf.tobytes()))
    rec.close()
    return prefix + ".rec", truths


def test_parse_det_label_and_errors():
    objs = image._parse_det_label(_packed_label([[1, .1, .2, .5, .6],
                                                 [0, 0, 0, 1, 1]]))
    assert objs.shape == (2, 5)
    np.testing.assert_allclose(objs[0], [1, .1, .2, .5, .6])
    with pytest.raises(mx.MXNetError):
        image._parse_det_label(np.array([9, 1, 2], np.float32))  # B < 5


def test_det_horizontal_flip_boxes():
    src = np.arange(4 * 4 * 3, dtype=np.uint8).reshape(4, 4, 3)
    label = np.array([[0, 0.1, 0.2, 0.4, 0.9]], np.float32)
    aug = image.DetHorizontalFlipAug(p=1.0)
    out, lab = aug(src, label)
    np.testing.assert_array_equal(out, src[:, ::-1])
    np.testing.assert_allclose(lab[0], [0, 0.6, 0.2, 0.9, 0.9], atol=1e-6)


def test_det_random_crop_keeps_covered_boxes(seeded):
    src = np.zeros((40, 40, 3), np.uint8)
    label = np.array([[2, 0.25, 0.25, 0.75, 0.75]], np.float32)
    aug = image.DetRandomCropAug(min_object_covered=0.9,
                                 area_range=(0.8, 1.0),
                                 min_eject_coverage=0.5, max_attempts=50)
    out, lab = aug(src, label)
    assert lab.shape[0] == 1                 # box survived
    assert (lab[:, 1:] >= 0).all() and (lab[:, 1:] <= 1).all()
    assert lab[0, 3] > lab[0, 1] and lab[0, 4] > lab[0, 2]


def test_det_random_pad_shrinks_boxes(seeded):
    src = np.full((20, 20, 3), 200, np.uint8)
    label = np.array([[1, 0.0, 0.0, 1.0, 1.0]], np.float32)
    aug = image.DetRandomPadAug(area_range=(1.5, 2.5), max_attempts=50)
    out, lab = aug(src, label)
    assert out.shape[0] >= 20 and out.shape[1] >= 20
    # the (full-image) box now covers a strict subset of the canvas
    w = lab[0, 3] - lab[0, 1]
    h = lab[0, 4] - lab[0, 2]
    assert w * h < 1.0
    assert (lab[:, 1:] >= 0).all() and (lab[:, 1:] <= 1).all()


def test_image_det_iter_over_records(tmp_path, seeded):
    rec_path, truths = _write_det_rec(tmp_path, n=10)
    it = image.ImageDetIter(batch_size=5, data_shape=(3, 32, 32),
                            path_imgrec=rec_path)
    assert it.label_shape == (1, 5)
    batches = list(it)
    assert len(batches) == 2
    seen = []
    for b in batches:
        assert b.data[0].shape == (5, 3, 32, 32)
        lab = b.label[0].asnumpy()
        assert lab.shape == (5, 1, 5)
        for row in lab[:, 0]:
            assert row[0] >= 0              # every record has one object
            assert (row[1:] >= 0).all() and (row[1:] <= 1).all()
            seen.append(tuple(np.round(row, 5)))
    # unshuffled: labels come back in record order
    np.testing.assert_allclose([s for s in seen],
                               np.asarray(truths, np.float32), atol=1e-5)


def test_image_det_iter_pads_variable_objects(tmp_path):
    import cv2
    prefix = os.path.join(str(tmp_path), "multi")
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    counts = [1, 3, 2]
    for i, cnt in enumerate(counts):
        img = np.zeros((24, 24, 3), np.uint8)
        boxes = [[c, 0.1 * (c + 1), 0.1, 0.1 * (c + 1) + 0.2, 0.4]
                 for c in range(cnt)]
        ok, buf = cv2.imencode(".png", img)
        rec.write_idx(i, recordio.pack(
            recordio.IRHeader(0, _packed_label(boxes), i, 0),
            buf.tobytes()))
    rec.close()
    it = image.ImageDetIter(batch_size=3, data_shape=(3, 24, 24),
                            path_imgrec=prefix + ".rec")
    assert it.label_shape == (3, 5)
    b = next(it)
    lab = b.label[0].asnumpy()
    for i, cnt in enumerate(counts):
        assert (lab[i, :cnt, 0] >= 0).all()
        assert (lab[i, cnt:, 0] == -1).all()   # -1 padding rows


def test_im2rec_pack_label_roundtrip(tmp_path):
    import importlib.util
    import cv2
    spec = importlib.util.spec_from_file_location(
        "im2rec", os.path.join(_ROOT, "tools", "im2rec.py"))
    im2rec = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(im2rec)

    root = os.path.join(str(tmp_path), "imgs")
    os.makedirs(root)
    for i in range(3):
        cv2.imwrite(os.path.join(root, f"im{i}.png"),
                    np.full((16, 16, 3), 50 * i, np.uint8))
    prefix = os.path.join(str(tmp_path), "detpack")
    with open(prefix + ".lst", "w") as f:
        for i in range(3):
            boxes = f"{4}\t{5}\t0\t0\t{i}\t0.1\t0.2\t0.5\t0.6"
            f.write(f"{i}\t{boxes}\tim{i}.png\n")
    n, skipped = im2rec.make_rec(prefix, root, pack_label=True)
    assert (n, skipped) == (3, 0)

    it = image.ImageDetIter(batch_size=3, data_shape=(3, 16, 16),
                            path_imgrec=prefix + ".rec")
    lab = next(it).label[0].asnumpy()
    np.testing.assert_allclose(lab[:, 0, 0], [0, 1, 2])
    np.testing.assert_allclose(lab[:, 0, 1:], [[0.1, 0.2, 0.5, 0.6]] * 3,
                               atol=1e-6)


def test_ssd_example_trains_from_records(tmp_path):
    """The SSD lane fed by PACKED RECORDS instead of synthetic arrays
    ('feed the SSD example from packed records')."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "train_ssd", os.path.join(_ROOT, "examples", "ssd", "train_ssd.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rec = mod.make_det_records(os.path.join(str(tmp_path), "shapes"),
                               n=96, size=32, seed=1)
    out = mod.run(batch=16, steps=40, log=False, from_records=rec)
    assert out["last_loss"] < out["first_loss"]
    assert out["mean_top_iou"] > 0.05


def test_image_det_iter_from_lst(tmp_path):
    """Packed .lst path keeps every box (label_width=-1 variable labels —
    review regression: a fixed width silently dropped all objects)."""
    import cv2
    root = os.path.join(str(tmp_path), "imgs")
    os.makedirs(root)
    for i in range(2):
        cv2.imwrite(os.path.join(root, f"a{i}.png"),
                    np.full((16, 16, 3), 90, np.uint8))
    lst = os.path.join(str(tmp_path), "det.lst")
    with open(lst, "w") as f:
        f.write("0\t4\t5\t0\t0\t1\t0.1\t0.2\t0.5\t0.6\ta0.png\n")
        f.write("1\t4\t5\t0\t0\t2\t0.3\t0.3\t0.9\t0.8\t0\t0\t0\t1\t1"
                "\ta1.png\n")
    it = image.ImageDetIter(batch_size=2, data_shape=(3, 16, 16),
                            path_imglist=lst, path_root=root)
    assert it.label_shape == (2, 5)
    lab = next(it).label[0].asnumpy()
    np.testing.assert_allclose(lab[0, 0], [1, 0.1, 0.2, 0.5, 0.6],
                               atol=1e-6)
    assert lab[0, 1, 0] == -1                       # padded slot
    np.testing.assert_allclose(lab[1, 1], [0, 0, 0, 1, 1], atol=1e-6)


def test_image_det_iter_truncates_wide_objects(tmp_path):
    """Records with B=6 extra attributes + explicit label_shape width 5:
    extra columns are truncated, not a broadcast crash (review
    regression)."""
    import cv2
    prefix = os.path.join(str(tmp_path), "wide")
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    img = np.zeros((16, 16, 3), np.uint8)
    ok, buf = cv2.imencode(".png", img)
    label = np.array([4, 6, 0, 0, 1, 0.1, 0.2, 0.5, 0.6, 0.77], np.float32)
    rec.write_idx(0, recordio.pack(recordio.IRHeader(0, label, 0, 0),
                                   buf.tobytes()))
    rec.close()
    it = image.ImageDetIter(batch_size=1, data_shape=(3, 16, 16),
                            path_imgrec=prefix + ".rec",
                            label_shape=(1, 5))
    lab = next(it).label[0].asnumpy()
    np.testing.assert_allclose(lab[0, 0], [1, 0.1, 0.2, 0.5, 0.6],
                               atol=1e-6)
