"""Native C++ loader: build, decode parity vs PIL, batch semantics.

`native/loader.cc` is the rebuild's first-party native component
(the reference has none in-tree, SURVEY.md §2.2 — its decode ran inside
torch DataLoader worker processes; ours is a C++ thread pool)."""

import os

import numpy as np
import pytest

PIL = pytest.importorskip("PIL")
from PIL import Image

from moco_tpu.data.native_loader import (
    NativeBatchLoader,
    NativeImageFolderDataset,
    native_available,
)

pytestmark = pytest.mark.skipif(not native_available(), reason="native loader not built")


@pytest.fixture(scope="module")
def image_dir(tmp_path_factory):
    """A tiny ImageFolder tree with JPEG + PNG of varied sizes."""
    root = tmp_path_factory.mktemp("imgs")
    rng = np.random.default_rng(0)
    sizes = [(64, 48), (48, 64), (100, 100), (37, 53)]
    paths = []
    for cls in ("a", "b"):
        (root / cls).mkdir()
        for i, (w, h) in enumerate(sizes):
            arr = rng.integers(0, 256, (h, w, 3), dtype=np.uint8)
            ext = "jpg" if i % 2 == 0 else "png"
            p = root / cls / f"img_{i}.{ext}"
            Image.fromarray(arr).save(p, quality=95)
            paths.append(str(p))
    return str(root), paths


def test_batch_shape_and_determinism(image_dir):
    root, paths = image_dir
    loader = NativeBatchLoader(paths, canvas=32, threads=4)
    idx = np.arange(len(paths))
    out1 = loader.load_batch(idx)
    out2 = loader.load_batch(idx)
    assert out1.shape == (len(paths), 32, 32, 3)
    assert out1.dtype == np.uint8
    np.testing.assert_array_equal(out1, out2)
    # images are non-degenerate (decode actually happened)
    assert out1.std() > 10


def test_decode_parity_with_pil(image_dir):
    """Native decode+resize+crop ≈ the Python ImageFolderDataset path.
    JPEG decoders and resamplers differ slightly; mean abs diff must be
    small (a few gray levels), which is invisible after augmentation."""
    from moco_tpu.data.datasets import ImageFolderDataset

    root, _ = image_dir
    py = ImageFolderDataset(root, decode_size=32)
    nat = NativeImageFolderDataset(root, decode_size=32)
    assert len(py) == len(nat)
    for i in range(len(py)):
        a, la = py.load(i)
        b, lb = nat.load(i)
        assert la == lb
        assert a.shape == b.shape == (32, 32, 3)
        diff = np.abs(a.astype(np.float32) - b.astype(np.float32)).mean()
        assert diff < 6.0, f"index {i}: mean abs diff {diff}"


def test_out_of_range_index_zero_fills(image_dir):
    root, paths = image_dir
    loader = NativeBatchLoader(paths, canvas=16, threads=2)
    with pytest.warns(UserWarning, match="failed to decode"):
        out = loader.load_batch(np.asarray([0, 10_000]))
    assert out[1].max() == 0  # failed slot zero-filled
    assert out[0].std() > 0


def test_unsupported_format_falls_back_to_pil(tmp_path):
    """Formats the C++ decoders lack (bmp) retry through PIL per slot —
    never silently-black frames."""
    root = tmp_path / "tree"
    (root / "a").mkdir(parents=True)
    rng = np.random.default_rng(3)
    arr = rng.integers(0, 256, (40, 56, 3), dtype=np.uint8)
    Image.fromarray(arr).save(root / "a" / "img.bmp")
    Image.fromarray(arr).save(root / "a" / "img.jpg", quality=95)
    nat = NativeImageFolderDataset(str(root), decode_size=32)
    from moco_tpu.data.datasets import ImageFolderDataset

    py = ImageFolderDataset(str(root), decode_size=32)
    for i in range(len(nat)):
        b, _ = nat.load(i)
        a, _ = py.load(i)
        assert b.std() > 5, "fallback produced a blank frame"
        diff = np.abs(a.astype(np.float32) - b.astype(np.float32)).mean()
        assert diff < 6.0


def test_decode_size_override_rejected(image_dir):
    root, _ = image_dir
    nat = NativeImageFolderDataset(root, decode_size=32)
    with pytest.raises(ValueError, match="fixed canvas"):
        nat.load(0, decode_size=64)


def test_labels_match_folder_classes(image_dir):
    root, _ = image_dir
    nat = NativeImageFolderDataset(root, decode_size=16)
    imgs, labels = nat.load_batch(np.arange(len(nat)))
    assert set(labels.tolist()) == {0, 1}
    assert imgs.shape[0] == len(nat)


def test_pipeline_uses_native_batch(image_dir):
    """TwoCropPipeline._host_batch must take the load_batch fast path."""
    import jax

    from moco_tpu.data.pipeline import TwoCropPipeline
    from moco_tpu.parallel import create_mesh
    from moco_tpu.utils.config import DataConfig

    root, _ = image_dir
    nat = NativeImageFolderDataset(root, decode_size=32)
    mesh = create_mesh(num_data=1, num_model=1, devices=jax.devices()[:1])
    cfg = DataConfig(dataset="imagefolder", data_dir=root, image_size=32, global_batch=4)
    pipe = TwoCropPipeline(cfg, mesh, dataset=nat)
    batch = next(iter(pipe.epoch(0)))
    assert batch["im_q"].shape == (4, 32, 32, 3)


def test_get_dims_matches_originals(image_dir):
    root, paths = image_dir
    loader = NativeBatchLoader(paths, canvas=32, threads=2)
    dims = loader.get_dims(np.arange(len(paths)))
    for i, p in enumerate(paths):
        with Image.open(p) as im:
            w, h = im.size
        assert tuple(dims[i]) == (h, w)
    # cached second call identical
    np.testing.assert_array_equal(dims, loader.get_dims(np.arange(len(paths))))


def test_load_crops_parity_with_pil(image_dir):
    """Native region-resize == PIL crop+resize (both BILINEAR antialias),
    for boxes sampled against ORIGINAL geometry — the exact-crop path of
    VERDICT r1 weak-item 6."""
    root, paths = image_dir
    loader = NativeBatchLoader(paths, canvas=32, threads=2)
    idx = np.arange(len(paths))
    dims = loader.get_dims(idx)
    from moco_tpu.data.datasets import sample_rrc_boxes

    rng = np.random.default_rng(3)
    boxes = np.stack(
        [sample_rrc_boxes(rng, dims), sample_rrc_boxes(rng, dims)], axis=1
    )
    out = loader.load_crops(idx, boxes, out_size=24)
    assert out.shape == (len(paths), 2, 24, 24, 3)
    for i, p in enumerate(paths):
        with Image.open(p) as im:
            im = im.convert("RGB")
            for c in range(2):
                y0, x0, ch, cw = boxes[i, c]
                want = np.asarray(
                    im.crop((x0, y0, x0 + cw, y0 + ch)).resize((24, 24), Image.BILINEAR),
                    np.float32,
                )
                diff = np.abs(out[i, c].astype(np.float32) - want).mean()
                assert diff < 6.0, f"img {i} crop {c}: mean abs diff {diff}"


def test_imagefolder_crop_protocol_parity(image_dir):
    """PIL ImageFolderDataset and NativeImageFolderDataset expose the same
    host-crop protocol with matching outputs."""
    from moco_tpu.data.datasets import ImageFolderDataset, sample_rrc_boxes

    root, _ = image_dir
    py = ImageFolderDataset(root, decode_size=32)
    nat = NativeImageFolderDataset(root, decode_size=32)
    idx = np.arange(len(py))
    np.testing.assert_array_equal(py.dims(idx), nat.dims(idx))
    boxes = sample_rrc_boxes(np.random.default_rng(0), py.dims(idx))[:, None]
    a, la = py.load_crop_batch(idx, boxes, 16)
    b, lb = nat.load_crop_batch(idx, boxes, 16)
    np.testing.assert_array_equal(la, lb)
    assert a.shape == b.shape == (len(py), 1, 16, 16, 3)
    diff = np.abs(a.astype(np.float32) - b.astype(np.float32)).mean()
    assert diff < 6.0


def test_decode_failures_counter(tmp_path):
    """Doubly-failed slots (native + PIL) zero-fill AND count — the
    `decode_failures` surface the pipeline reports (fault-tolerance
    layer); recoverable PIL-fallback slots do not count."""
    root = tmp_path / "imgs"
    (root / "a").mkdir(parents=True)
    rng = np.random.default_rng(0)
    arr = rng.integers(0, 256, (40, 40, 3), dtype=np.uint8)
    Image.fromarray(arr).save(root / "a" / "good.jpg", quality=95)
    (root / "a" / "corrupt.jpg").write_bytes(b"\xff\xd8\xff definitely not jpeg")

    ds = NativeImageFolderDataset(str(root), decode_size=32, threads=2)
    assert ds.decode_failures == 0
    with pytest.warns(UserWarning, match="failed to decode"):
        imgs, _ = ds.load_batch(np.arange(len(ds)))
    assert ds.decode_failures == 1
    # the good slot decoded, the corrupt one zero-filled
    sums = imgs.reshape(len(ds), -1).sum(axis=1)
    assert (sums == 0).sum() == 1 and (sums > 0).sum() == 1


def test_library_is_rebuilt_when_sources_change(monkeypatch):
    """The git-ignored .so is keyed on the content of loader.cc and the
    Makefile: one left behind by an older checkout (here: a stamp that
    names other sources) is rebuilt, not loaded because it exists."""
    from moco_tpu.data import native_loader as nl

    key = nl._source_key()
    assert nl._built_from(key)  # the module-level skipif already built it
    with open(nl._STAMP_PATH, "w") as f:
        f.write("0" * 64 + "\n")
    assert not nl._built_from(key)
    before = os.stat(nl._LIB_PATH).st_mtime_ns
    monkeypatch.setattr(nl, "_lib", None)
    nl._load_lib()
    assert nl._built_from(key)
    assert os.stat(nl._LIB_PATH).st_mtime_ns > before
