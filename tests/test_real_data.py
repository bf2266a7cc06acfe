"""Real-dataset ingestion (VERDICT r1 missing-#3): ImageNet folder decoded by
libjpeg-turbo through PIL (the native JPEG decoder where PIL is absent),
Criteo TSV, Wikipedia dumps.

Fixtures are generated with independent encoders (PIL JPEG, hand-written XML)
so the parity is against a second implementation, not our own round-trip.
"""

import contextlib
import io
import os
import sys
from unittest import mock

import numpy as np
import pytest

from distributeddeeplearningspark_tpu.data import vision
from distributeddeeplearningspark_tpu.data.sources import (
    CRITEO_DENSE,
    CRITEO_SPARSE,
    criteo_tsv,
    imagenet_folder,
)
from distributeddeeplearningspark_tpu.data.text import clean_wikitext, wikipedia_dump
from distributeddeeplearningspark_tpu.utils import native

PIL = pytest.importorskip("PIL.Image")


def _jpeg_bytes(arr: np.ndarray, *, subsampling=0, quality=90, **kw) -> bytes:
    img = PIL.fromarray(arr if arr.ndim == 3 else arr, "RGB" if arr.ndim == 3 else "L")
    buf = io.BytesIO()
    img.save(buf, format="JPEG", quality=quality, subsampling=subsampling, **kw)
    return buf.getvalue()


def _smooth(h, w, c=3, seed=0):
    """Genuinely smooth content (gaussian-filtered noise): chroma-upsampling
    differences between decoders vanish away from hard edges."""
    from scipy.ndimage import gaussian_filter

    rng = np.random.default_rng(seed)
    base = rng.normal(128, 60, (h, w, c))
    sm = gaussian_filter(base, sigma=(3, 3, 0))
    return np.clip(sm, 0, 255).astype(np.uint8)


def _pil_decode(data: bytes) -> np.ndarray:
    arr = np.asarray(PIL.open(io.BytesIO(data)).convert("RGB"))
    return arr


# -- native JPEG decoder -----------------------------------------------------

def test_native_jpeg_444_matches_pil_closely():
    data = _jpeg_bytes(_smooth(96, 128), subsampling=0)
    got = native.jpeg_decode(data)
    assert got is not None, "native library failed to build"
    want = _pil_decode(data)
    diff = np.abs(got.astype(int) - want.astype(int))
    assert got.shape == want.shape
    assert diff.max() <= 4, f"max diff {diff.max()}"  # IDCT rounding only


@pytest.mark.parametrize("subsampling,hw", [(2, (120, 200)), (1, (64, 96)),
                                            (2, (251, 133))])
def test_native_jpeg_subsampled_close_to_pil(subsampling, hw):
    data = _jpeg_bytes(_smooth(*hw, seed=subsampling), subsampling=subsampling)
    got = native.jpeg_decode(data)
    want = _pil_decode(data)
    diff = np.abs(got.astype(int) - want.astype(int))
    assert got.shape == want.shape
    # box vs triangle chroma upsampling differs at edges; content is smooth
    assert diff.mean() < 1.5 and diff.max() <= 48, (diff.mean(), diff.max())


def test_native_jpeg_grayscale():
    arr = _smooth(80, 60, c=1, seed=7)[..., 0]
    data = _jpeg_bytes(arr)
    got = native.jpeg_decode(data)
    assert got.shape == (80, 60, 1)
    want = np.asarray(PIL.open(io.BytesIO(data)).convert("L"))[..., None]
    assert np.abs(got.astype(int) - want.astype(int)).max() <= 2


def test_native_jpeg_progressive_rejected_and_vision_decodes_it():
    arr = _smooth(48, 48, seed=3)
    data = _jpeg_bytes(arr, progressive=True)
    with pytest.raises(native.JpegUnsupported):
        native.jpeg_decode(data)
    # the public decode path never asks the native decoder while PIL imports
    out = vision.decode_jpeg(data)
    np.testing.assert_array_equal(out, _pil_decode(data))


def test_native_jpeg_malformed_raises():
    with pytest.raises(ValueError):
        native.jpeg_decode(b"\xff\xd8\xff\xe0not a real jpeg at all")


def test_native_jpeg_batch_matches_single():
    datas = [_jpeg_bytes(_smooth(64 + 8 * i, 80, seed=i)) for i in range(5)]
    batch = native.jpeg_decode_batch(datas)
    assert batch is not None
    for d, got in zip(datas, batch):
        np.testing.assert_array_equal(got, native.jpeg_decode(d))


# -- vision.decode_jpeg: PIL's pixels, the native decoder's where PIL is absent -

def _cmyk_jpeg_bytes(arr: np.ndarray) -> bytes:
    buf = io.BytesIO()
    PIL.fromarray(arr, "RGB").convert("CMYK").save(buf, format="JPEG",
                                                   quality=90)
    return buf.getvalue()


_JPEG_KINDS = {
    "444": lambda: _jpeg_bytes(_smooth(96, 128), subsampling=0),
    "422": lambda: _jpeg_bytes(_smooth(64, 96, seed=1), subsampling=1),
    "420_odd_size": lambda: _jpeg_bytes(_smooth(251, 133, seed=2),
                                        subsampling=2),
    "grayscale": lambda: _jpeg_bytes(_smooth(80, 60, c=1, seed=7)[..., 0]),
    "progressive": lambda: _jpeg_bytes(_smooth(48, 48, seed=3),
                                       subsampling=2, progressive=True),
    "cmyk": lambda: _cmyk_jpeg_bytes(_smooth(40, 56, seed=4)),
}


def _without_pil():
    """``import PIL`` fails inside, as where it is not installed. Make the
    test's JPEGs before entering: PIL loads its plugins by import."""
    return mock.patch.dict(sys.modules, {"PIL": None})


@pytest.mark.parametrize("source", ["bytes", "path"])
@pytest.mark.parametrize("kind", list(_JPEG_KINDS))
def test_decode_jpeg_returns_pils_pixels(kind, source, tmp_path):
    data = _JPEG_KINDS[kind]()
    img = PIL.open(io.BytesIO(data))
    want = np.asarray(img if img.mode == "L" else img.convert("RGB"))
    if want.ndim == 2:
        want = want[..., None]
    if source == "path":
        (tmp_path / "x.JPEG").write_bytes(data)
        got = vision.decode_jpeg(str(tmp_path / "x.JPEG"))
    else:
        got = vision.decode_jpeg(data)
    assert got.dtype == np.uint8 and got.ndim == 3
    assert got.shape[-1] == (1 if kind == "grayscale" else 3)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kind", ["444", "422", "420_odd_size", "grayscale"])
def test_decode_jpeg_without_pil_returns_the_native_decoders(kind):
    data = _JPEG_KINDS[kind]()
    want = native.jpeg_decode(data)
    assert want is not None, "native library failed to build"
    with _without_pil():
        with pytest.raises(ImportError):
            from PIL import Image  # noqa: F401
        got = vision.decode_jpeg(data)
    np.testing.assert_array_equal(got, want)
    if kind == "420_odd_size":  # the two decoders are two: chroma differs
        assert (got != vision.decode_jpeg(data)).any()


def test_decode_jpeg_without_pil_refuses_progressive_by_name(tmp_path):
    (tmp_path / "p.jpg").write_bytes(_JPEG_KINDS["progressive"]())
    with _without_pil(), pytest.raises(native.JpegUnsupported, match="p.jpg"):
        vision.decode_jpeg(tmp_path / "p.jpg")


_MALFORMED = {
    "no_jpeg_at_all": lambda: b"\xff\xd8\xff\xe0not a real jpeg at all",
    "cut_in_the_tables": lambda: _JPEG_KINDS["444"]()[:300],
    "empty": lambda: b"",
    # libjpeg-turbo through PIL refuses a scan that ends early; the native
    # decoder pads it, as it always has
    "cut_in_the_scan": lambda: _JPEG_KINDS["444"]()[:1500],
}


@pytest.mark.parametrize("what,route", [
    *((w, r) for w in list(_MALFORMED)[:3] for r in ("pil", "native")),
    ("cut_in_the_scan", "pil")])
def test_decode_jpeg_malformed_raises_value_error(what, route, tmp_path):
    data = _MALFORMED[what]()
    (tmp_path / "bad.JPEG").write_bytes(data)
    with _without_pil() if route == "native" else contextlib.nullcontext():
        with pytest.raises(ValueError):
            vision.decode_jpeg(data)
        with pytest.raises(ValueError, match="bad.JPEG"):
            vision.decode_jpeg(str(tmp_path / "bad.JPEG"))
        # a file that is not there is no malformed stream
        with pytest.raises(FileNotFoundError):
            vision.decode_jpeg(str(tmp_path / "nope.JPEG"))


# -- ImageNet folder ---------------------------------------------------------

def _make_imagenet(tmp_path, n_per_class=3):
    for ci, cname in enumerate(["n01440764", "n01443537"]):
        d = tmp_path / cname
        d.mkdir()
        for j in range(n_per_class):
            arr = _smooth(72 + 8 * j, 96, seed=ci * 10 + j)
            (d / f"{cname}_{j}.JPEG").write_bytes(_jpeg_bytes(arr))
    return tmp_path


def test_imagenet_folder_loads_and_labels(tmp_path):
    root = _make_imagenet(tmp_path)
    ds = imagenet_folder(str(root), num_partitions=2)
    examples = ds.collect()
    assert len(examples) == 6
    labels = sorted(int(e["label"]) for e in examples)
    assert labels == [0, 0, 0, 1, 1, 1]  # sorted-dir-order convention
    for e in examples:
        assert e["image"].dtype == np.uint8 and e["image"].shape[-1] == 3


def test_imagenet_folder_trains_through_pipeline(tmp_path):
    from distributeddeeplearningspark_tpu.data.feed import host_batches

    root = _make_imagenet(tmp_path)
    ds = vision.imagenet_train(imagenet_folder(str(root), num_partitions=2),
                               size=32, seed=0)
    batches = list(host_batches(ds, 4, num_shards=2))
    assert batches and batches[0]["image"].shape == (4, 32, 32, 3)
    assert batches[0]["image"].dtype == np.float32


def test_imagenet_folder_raw_bytes_mode(tmp_path):
    """``decode=False`` hands the file over unread; its bytes are read where
    the map needs them."""
    from distributeddeeplearningspark_tpu.data.sources import EncodedFile

    root = _make_imagenet(tmp_path)
    ds = imagenet_folder(str(root), num_partitions=1, decode=False)
    e = ds.take(1)[0]
    assert isinstance(e["jpeg"], EncodedFile)
    assert bytes(e["jpeg"])[:2] == b"\xff\xd8"
    assert vision.decode_jpeg(e["jpeg"]).shape[-1] == 3


@pytest.mark.parametrize("how", ["thread_pool", "two_worker_processes"])
def test_imagenet_train_examples_identical_to_the_serial_map(tmp_path, how):
    """The crop and flip are seeded by the decoded bytes, so every route has
    to decode alike: the serial map in this thread, ``map_parallel``'s pool
    and two worker processes all call the one ``decode_jpeg``."""
    root = _make_imagenet(tmp_path, n_per_class=4)

    def examples(**route):
        ds = vision.imagenet_train(
            imagenet_folder(str(root), num_partitions=2, decode=False),
            size=32, seed=3, **route)
        return [e for i in range(ds.num_partitions)
                for e in ds.iter_partition(i)]

    want = examples(num_threads=0)
    got = examples(**({"num_threads": 4} if how == "thread_pool"
                      else {"num_workers": 2}))
    assert len(got) == len(want) == 8
    for a, b in zip(got, want):
        assert a.keys() == b.keys() == {"image", "label"}
        assert int(a["label"]) == int(b["label"])
        assert a["image"].shape == (32, 32, 3)
        assert np.asarray(a["image"]).tobytes() == b["image"].tobytes()


def test_imagenet_folder_missing_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        imagenet_folder(str(tmp_path / "nope"))
    (tmp_path / "empty").mkdir()
    with pytest.raises(FileNotFoundError):
        imagenet_folder(str(tmp_path / "empty"))


# -- Criteo TSV --------------------------------------------------------------

def _criteo_line(rng, missing=False):
    label = rng.integers(0, 2)
    dense = ["" if (missing and i == 3) else str(rng.integers(0, 1000))
             for i in range(CRITEO_DENSE)]
    cats = ["" if (missing and i == 5) else format(rng.integers(0, 1 << 32), "08x")
            for i in range(CRITEO_SPARSE)]
    return "\t".join([str(label), *dense, *cats])


def test_criteo_tsv_parses_schema(tmp_path):
    rng = np.random.default_rng(0)
    lines = [_criteo_line(rng, missing=(i % 3 == 0)) for i in range(50)]
    f = tmp_path / "day_0.txt"
    f.write_text("\n".join(lines) + "\n")
    ds = criteo_tsv(str(f), vocab_sizes=(1000,) * CRITEO_SPARSE)
    examples = ds.collect()
    assert len(examples) == 50
    e = examples[0]
    assert e["dense"].shape == (CRITEO_DENSE,) and e["dense"].dtype == np.float32
    assert e["sparse"].shape == (CRITEO_SPARSE,) and e["sparse"].dtype == np.int32
    assert all(0 <= s < 1000 for s in e["sparse"])
    assert int(e["label"]) in (0, 1)
    # missing dense → 0.0; missing categorical → bucket 0
    miss = examples[0]
    assert miss["dense"][3] == 0.0 and miss["sparse"][5] == 0


def test_criteo_tsv_byte_splits_cover_every_line_once(tmp_path):
    """A >1MB file splits by byte ranges; the union of partitions must be
    exactly the file's lines (the Spark TextInputFormat contract)."""
    rng = np.random.default_rng(1)
    n = 12000
    f = tmp_path / "big.txt"
    f.write_text("\n".join(_criteo_line(rng) for _ in range(n)) + "\n")
    assert f.stat().st_size > (1 << 20)
    ds = criteo_tsv(str(f), num_partitions=4, vocab_sizes=(1 << 16,) * CRITEO_SPARSE)
    assert ds.num_partitions >= 4
    total = sum(len(list(ds.iter_partition(i))) for i in range(ds.num_partitions))
    assert total == n


def test_criteo_tsv_trains_dlrm_batch(tmp_path, eight_devices):
    import jax
    import optax

    from distributeddeeplearningspark_tpu.data.feed import put_global, stack_examples
    from distributeddeeplearningspark_tpu.models import DLRM
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec
    from distributeddeeplearningspark_tpu.parallel.sharding import REPLICATED
    from distributeddeeplearningspark_tpu.train import losses, step as step_lib

    rng = np.random.default_rng(2)
    f = tmp_path / "c.txt"
    f.write_text("\n".join(_criteo_line(rng) for _ in range(16)) + "\n")
    vocab = (64,) * CRITEO_SPARSE
    ds = criteo_tsv(str(f), vocab_sizes=vocab)
    batch = stack_examples(ds.take(8))
    mesh = MeshSpec(data=2).build(eight_devices[:2])
    model = DLRM(vocab_sizes=vocab, embed_dim=8, bottom_mlp=(16, 8), top_mlp=(8, 1))
    state, sh = step_lib.init_state(model, optax.sgd(0.1), batch, mesh, REPLICATED)
    step = step_lib.jit_train_step(
        step_lib.make_train_step(model.apply, optax.sgd(0.1), losses.binary_xent),
        mesh, sh)
    _, metrics = step(state, put_global(batch, mesh))
    assert np.isfinite(float(jax.device_get(metrics["loss"])))


# -- Wikipedia dumps ---------------------------------------------------------

_XML_DUMP = """<mediawiki xmlns="http://www.mediawiki.org/xml/export-0.10/">
  <page>
    <title>Alpha</title>
    <revision><text>'''Alpha''' is the [[first letter|first]] letter of the
[[Greek alphabet]].{{Infobox|foo=bar}} It has been used since the
[[8th century BC]] in ancient texts.&lt;ref&gt;cite&lt;/ref&gt; More prose
follows here so the document clears the minimum length filter easily.</text></revision>
  </page>
  <page>
    <title>Redirect me</title>
    <redirect title="Alpha"/>
    <revision><text>#REDIRECT [[Alpha]]</text></revision>
  </page>
  <page>
    <title>Beta</title>
    <revision><text>Beta is the second letter. {{stub}} It follows
[[Alpha|alpha]] and precedes gamma in the traditional ordering of the
alphabet, and this sentence pads the document past the length filter.</text></revision>
  </page>
</mediawiki>
"""


def test_wikipedia_xml_dump(tmp_path):
    f = tmp_path / "enwiki-test.xml"
    f.write_text(_XML_DUMP)
    docs = wikipedia_dump(str(f), num_partitions=2).collect()
    assert len(docs) == 2  # redirect skipped
    joined = " ".join(docs)
    assert "Greek alphabet" in joined and "first letter" not in joined.replace(
        "first letter of", "KEEP")  # [[a|b]] unwrapped to b
    assert "{{" not in joined and "[[" not in joined and "'''" not in joined


def test_wikipedia_xml_bz2(tmp_path):
    import bz2

    f = tmp_path / "enwiki-test.xml.bz2"
    f.write_bytes(bz2.compress(_XML_DUMP.encode()))
    docs = wikipedia_dump(str(f)).collect()
    assert len(docs) == 2


def test_wikipedia_wikiextractor_tree(tmp_path):
    d = tmp_path / "AA"
    d.mkdir()
    (d / "wiki_00").write_text(
        '<doc id="1" title="A">\nAlpha doc body, long enough to pass the '
        "minimum character filter for documents.\n</doc>\n"
        '<doc id="2" title="B">\nBeta doc body, also made long enough to '
        "pass the minimum character filter here.\n</doc>\n")
    docs = wikipedia_dump(str(tmp_path)).collect()
    assert len(docs) == 2
    assert all("<doc" not in doc for doc in docs)


def test_wikipedia_plain_text(tmp_path):
    f = tmp_path / "corpus.txt"
    f.write_text(
        "A single long line that is definitely over the minimum character "
        "limit for a document to be yielded.\n"
        "short line one\nshort line two\nshort line three which together "
        "with its siblings forms one long merged paragraph\n"
        "\n")
    docs = wikipedia_dump(str(f)).collect()
    assert len(docs) == 2  # long line + merged paragraph


def test_wikipedia_feeds_mlm_pipeline(tmp_path):
    from distributeddeeplearningspark_tpu.data.text import (
        WordPieceTokenizer,
        mlm_dataset,
    )

    f = tmp_path / "enwiki-test.xml"
    f.write_text(_XML_DUMP)
    docs = wikipedia_dump(str(f), num_partitions=2)
    tok = WordPieceTokenizer.train(docs.collect(), vocab_size=256)
    ds = mlm_dataset(docs, tok, seq_len=32)
    e = ds.take(1)[0]
    assert e["input_ids"].shape == (32,)
    assert set(e) >= {"input_ids", "attention_mask", "mlm_labels", "mlm_weights"}


def test_clean_wikitext_handles_nested_templates():
    s = "Keep {{outer {{inner}} more}} this and {{a|b}} that."
    out = clean_wikitext(s)
    assert "{{" not in out and "Keep" in out and "this and" in out


def test_eval_transform_resize_scales_with_crop_size():
    """The shorter-side resize must track the crop (ratio 0.875, the 256→224
    recipe generalized). A fixed 256 would zoom a 64-crop onto the central
    24×24 of the source — measured as a 1.0-train/0.28-eval accuracy split
    on a memorized set before the fix."""
    import numpy as np

    from distributeddeeplearningspark_tpu.data.vision import eval_transform

    # image with a bright left half: a correct 64/73 resize+center-crop keeps
    # roughly half the crop bright; a 256 resize would see only the center
    img = np.zeros((96, 96, 3), np.uint8)
    img[:, :48] = 255
    out = eval_transform(size=64)({"image": img, "label": 0})["image"]
    assert out.shape == (64, 64, 3)
    bright = (out[:, :, 0] > 0.0).mean()  # normalized: bright ≫ dark
    assert 0.35 < bright < 0.65, bright  # ~half, not all-or-nothing
