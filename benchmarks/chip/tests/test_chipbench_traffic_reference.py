"""The chip benchmark's generator and plain reference, on the CPU."""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import BenchError, reference, traffic  # noqa: E402

BIG_SEED = 2**40 + 12345  # the driver's seeds run past 32 bits


def content(name, h, w, count, seed, dtype="uint8"):
    """``count`` images from ``content/<name>.py``, as a configuration names it."""
    image = {"height": h, "width": w, "dtype": dtype, "content": name}
    return traffic.image_pool({"image": image}, count, seed, BENCH)


def frames(h, w, count, seed):
    return content("smooth_frames", h, w, count, seed)


def pages(h, w, count, seed):
    return content("scanned_pages", h, w, count, seed)


def test_same_seed_same_requests():
    for fn in (lambda s: traffic.kind_sequence(10, 500, s),
               lambda s: traffic.open_loop_due(400.0, 10.0, s),
               lambda s: frames(60, 80, 3, s),
               lambda s: pages(400, 300, 2, s)):
        np.testing.assert_array_equal(fn(BIG_SEED), fn(BIG_SEED))
        assert not np.array_equal(fn(BIG_SEED), fn(BIG_SEED + 1))


def test_seeds_share_the_work_in_another_order():
    a, b = traffic.kind_sequence(10, 500, 1), traffic.kind_sequence(10, 500, 2)
    assert np.bincount(a).tolist() == np.bincount(b).tolist() == [50] * 10
    ga = np.diff(traffic.open_loop_due(400.0, 10.0, 1))
    gb = np.diff(traffic.open_loop_due(400.0, 10.0, 2))
    assert len(ga) == len(gb) == 3999
    np.testing.assert_allclose(np.sort(np.r_[ga, 10.0 - ga.sum()]),
                               np.sort(np.r_[gb, 10.0 - gb.sum()]), rtol=1e-9)


def test_longer_sequence_begins_with_shorter():
    np.testing.assert_array_equal(traffic.kind_sequence(10, 1000, 9)[:300],
                                  traffic.kind_sequence(10, 300, 9))


def test_stamp_makes_each_request_unique():
    base = frames(60, 80, 1, 3)[0]
    a, b = traffic.stamp(base, 5), traffic.stamp(base, 6)
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a, traffic.stamp(base, 5))
    assert np.count_nonzero(a != base) <= 8


def test_request_kinds_expand_the_configuration():
    cfg = {"requests": [{"ops": ["erode", "dilate"], "se_sizes": [3, 15]},
                        {"plan": "document_cleanup"}]}
    assert traffic.request_kinds(cfg) == [
        {"op": "erode", "se": [3, 3]}, {"op": "erode", "se": [15, 15]},
        {"op": "dilate", "se": [3, 3]}, {"op": "dilate", "se": [15, 15]},
        {"plan": "document_cleanup"}]


def test_erosion_by_hand_at_the_borders():
    x = np.array([[9, 8, 7, 6],
                  [5, 4, 3, 2],
                  [1, 0, 9, 9]], dtype=np.uint8)
    # 3x3 window clipped to the image: the border outside is ignored
    want = np.array([[4, 3, 2, 2],
                     [0, 0, 0, 2],
                     [0, 0, 0, 2]], dtype=np.uint8)
    np.testing.assert_array_equal(reference.erode(x, (3, 3)), want)
    want_d = np.array([[9, 9, 8, 7],
                       [9, 9, 9, 9],
                       [5, 9, 9, 9]], dtype=np.uint8)
    np.testing.assert_array_equal(reference.dilate(x, (3, 3)), want_d)
    # a window wider than the image reaches every pixel of its rows
    np.testing.assert_array_equal(reference.erode(x, (1, 9)),
                                  np.array([[6] * 4, [2] * 4, [0] * 4], np.uint8))
    # the corner of a 255 image stays 255: no zero leaks in from the border
    full = np.full((5, 5), 255, np.uint8)
    np.testing.assert_array_equal(reference.erode(full, (5, 5)), full)
    np.testing.assert_array_equal(reference.dilate(np.zeros((5, 5), np.uint8), (5, 5)),
                                  np.zeros((5, 5), np.uint8))


def test_reference_against_brute_force():
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, (23, 31), dtype=np.uint8)
    for se in ((3, 3), (5, 7), (1, 15), (41, 41)):
        rh, rw = se[0] // 2, se[1] // 2
        want = np.empty_like(x)
        for i in range(x.shape[0]):
            for j in range(x.shape[1]):
                want[i, j] = x[max(0, i - rh):i + rh + 1, max(0, j - rw):j + rw + 1].min()
        np.testing.assert_array_equal(reference.erode(x, se), want)


def test_plan_steps_and_gradient():
    rng = np.random.default_rng(1)
    x = rng.integers(0, 256, (40, 50), dtype=np.uint8)
    steps = [{"op": "opening", "se": [3, 3]},
             {"op": "closing", "se": [5, 5], "save_as": "clean"},
             {"op": "gradient", "se": [3, 3], "save_as": "edges", "astype": "uint8"}]
    outs = reference.run_steps(x, steps)
    opened = reference.dilate(reference.erode(x, (3, 3)), (3, 3))
    clean = reference.erode(reference.dilate(opened, (5, 5)), (5, 5))
    np.testing.assert_array_equal(outs["clean"], clean)
    g = reference.gradient(clean, (3, 3))
    assert g.dtype == np.int32 and g.min() >= 0
    np.testing.assert_array_equal(outs["edges"], g.astype(np.uint8))
    assert outs["edges"].dtype == np.uint8


def test_even_se_is_refused():
    with pytest.raises(ValueError):
        reference.erode(np.zeros((4, 4), np.uint8), (2, 3))


def test_mismatches_counts_missing_outputs():
    want = {"out": np.zeros((3, 4), np.uint8)}
    assert reference.mismatches({"out": np.zeros((3, 4), np.uint8)}, want) == 0
    got = np.zeros((3, 4), np.uint8)
    got[1, 2] = 1
    assert reference.mismatches({"out": got}, want) == 1
    assert reference.mismatches({}, want) == 12
    assert reference.mismatches({"out": np.zeros((3, 4), np.int16)}, want) == 12


def test_control_differs_from_reference():
    x = frames(60, 80, 1, 4)[0]
    kind = {"op": "erode", "se": [15, 15]}
    assert reference.mismatches(reference.control(x, kind, {}),
                                reference.expected(x, kind, {})) > 0


# ------------------------------------------------------ content found by name
CONFIGS = os.path.join(BENCH, "configs")
# SHA-256 of each configuration's image pool, at the size its cell's traffic
# asks for, as the generators made it before they moved under content/
POOL_SHA256 = {
    ("paper_800x600_u8", 7): "4ba2fc9a8bf47fc717eb577326fa06687503ba306a6e8c5a49811e931fe76b81",
    ("paper_800x600_u8", BIG_SEED): "54d3fdb4658ccf6721a073097e73cf156220dba43b30ff682d44efd057a80c91",
    ("a4_300dpi_u8", 7): "df7813110db7c35221a32d6dc25661a6109af1337226e8b3ba67f115187fc48a",
    ("a4_300dpi_u8", BIG_SEED): "47a50b024aaedc7ed5ae97b9177c9150771a769d534217809df735535a600b4b",
    ("paper_800x600_u8_x4", 7): "4ba2fc9a8bf47fc717eb577326fa06687503ba306a6e8c5a49811e931fe76b81",
    ("paper_800x600_u8_x4", BIG_SEED): "54d3fdb4658ccf6721a073097e73cf156220dba43b30ff682d44efd057a80c91",
}
POOL_COUNT = {"paper_800x600_u8": 8, "a4_300dpi_u8": 2, "paper_800x600_u8_x4": 8}


def load_config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,seed", sorted(POOL_SHA256, key=str))
def test_image_pool_is_byte_identical_to_before(name, seed):
    pool = traffic.image_pool(load_config(name), POOL_COUNT[name], seed, BENCH)
    assert hashlib.sha256(pool.tobytes()).hexdigest() == POOL_SHA256[(name, seed)]


def test_missing_content_is_an_error():
    with pytest.raises(BenchError, match="no content generator 'no_such_content'"):
        content("no_such_content", 8, 8, 1, 3)


@pytest.mark.parametrize("made", [
    "np.zeros((count, 6, 8), np.uint16)",      # another pixel type
    "np.zeros((count, 6, 9), np.uint8)",       # another shape
    "np.zeros((count - 1, 6, 8), np.uint8)",   # too few images
    "np.zeros((count, 6, 8), np.uint8).tolist()",  # not an array
])
def test_content_unlike_the_configuration_is_an_error(tmp_path, made):
    (tmp_path / "content").mkdir()
    (tmp_path / "content" / "unlike.py").write_text(
        f"import numpy as np\n\ndef make(image, count, seed):\n    return {made}\n")
    image = {"height": 6, "width": 8, "dtype": "uint8", "content": "unlike"}
    with pytest.raises(BenchError, match="the configuration states uint8 \\(3, 6, 8\\)"):
        traffic.image_pool({"image": image}, 3, 1, str(tmp_path))


# ------------------------------------------------ every pixel type the service takes
def random_image(dtype, shape, seed):
    rng = np.random.default_rng(seed)
    if dtype == np.bool_:
        return rng.random(shape) < 0.6
    info = np.iinfo(dtype)
    return rng.integers(info.min, int(info.max) + 1, shape, dtype=dtype)


def brute(x, se, fn):
    rh, rw = se[0] // 2, se[1] // 2
    out = np.empty_like(x)
    for i in range(x.shape[0]):
        for j in range(x.shape[1]):
            out[i, j] = fn(x[max(0, i - rh):i + rh + 1, max(0, j - rw):j + rw + 1])
    return out


@pytest.mark.parametrize("dtype", [np.uint16, np.int16, np.int8, np.bool_])
def test_reference_against_brute_force_on_every_type(dtype):
    x = random_image(dtype, (17, 23), 5)
    for se in ((3, 3), (5, 7), (1, 9), (31, 31)):
        lo, hi = brute(x, se, np.min), brute(x, se, np.max)
        np.testing.assert_array_equal(reference.erode(x, se), lo)
        np.testing.assert_array_equal(reference.dilate(x, se), hi)
        g = reference.gradient(x, se)
        assert g.dtype == np.int32
        np.testing.assert_array_equal(g, hi.astype(np.int32) - lo.astype(np.int32))
        assert reference.erode(x, se).dtype == x.dtype


def test_bool_border_is_ignored():
    """Erosion pads a mask with True and dilation with False: a full mask
    erodes to itself and an empty one dilates to itself."""
    full = np.ones((5, 6), bool)
    np.testing.assert_array_equal(reference.erode(full, (5, 5)), full)
    np.testing.assert_array_equal(reference.dilate(~full, (5, 5)), ~full)
    np.testing.assert_array_equal(reference.OPS["opening"](full, (3, 3)), full)


# SHA-256 over every request kind of the control's outputs, on one image of
# each 8-bit content at a small size, as the control made them on the top four
# bits before it took other pixel types
CONTROL_SHA256 = {
    "paper_800x600_u8": ((120, 160), "e226a8d8f67e9e5346a3f8b7dd3aef8b5a9e7407d6a17315ba81931b512bee1b"),
    "a4_300dpi_u8": ((400, 300), "3a457d4baa616fa1c01bca75fe40b7722f004c87983fb5a4a0d96a3b03d08674"),
}


@pytest.mark.parametrize("name", sorted(CONTROL_SHA256))
def test_control_on_u8_is_the_top_four_bits(name):
    """On 8-bit pixels the control is bit for bit what it was: the reference
    on the pixels' top four bits, over both 8-bit contents and every kind."""
    cfg = load_config(name)
    (h, w), digest = CONTROL_SHA256[name]
    x = traffic.image_pool({"image": {**cfg["image"], "height": h, "width": w}}, 1,
                           2**40 + 3, BENCH)[0]
    plans = cfg.get("plans", {})
    total = hashlib.sha256()
    for k in traffic.request_kinds(cfg):
        got, want = reference.control(x, k, plans), reference.expected(x & np.uint8(0xF0), k, plans)
        assert got.keys() == want.keys()
        one = hashlib.sha256()
        for out in sorted(want):
            assert got[out].dtype == want[out].dtype
            np.testing.assert_array_equal(got[out], want[out])
            one.update(out.encode() + got[out].dtype.str.encode() + got[out].tobytes())
        total.update(one.hexdigest().encode())
    assert total.hexdigest() == digest


@pytest.mark.parametrize("dtype,pixels,kept", [
    (np.uint8, [0x00, 0x1F, 0xF0, 0xFF], [0x00, 0x10, 0xF0, 0xF0]),
    (np.uint16, [0x00FF, 0x1234, 0xFFFF], [0x0000, 0x1200, 0xFF00]),
    (np.int8, [-1, 0x1F, -128], [-16, 0x10, -128]),
    (np.int16, [-1, 0x1234, -32768], [-256, 0x1200, -32768]),
])
def test_control_keeps_the_top_half_of_the_bits(dtype, pixels, kept):
    x = np.array([pixels], dtype=dtype)
    got = reference.control(x, {"op": "erode", "se": [1, 1]}, {})["out"]
    assert got.dtype == dtype
    np.testing.assert_array_equal(got, np.array([kept], dtype=dtype))


def test_control_on_bool_narrows_each_window():
    x = random_image(np.bool_, (20, 30), 9)
    plans = {"p": [{"op": "opening", "se": [5, 3]},
                   {"op": "gradient", "se": [1, 7], "save_as": "edges"}]}
    np.testing.assert_array_equal(
        reference.control(x, {"plan": "p"}, plans)["edges"],
        reference.gradient(reference.OPS["opening"](x, (3, 1)), (1, 5)))
    np.testing.assert_array_equal(
        reference.control(x, {"op": "dilate", "se": [7, 3]}, {})["out"],
        reference.dilate(x, (5, 1)))
    assert plans["p"][0]["se"] == [5, 3]  # the configuration's plans are left as they were


@pytest.mark.parametrize("dtype", [np.uint8, np.uint16, np.int16, np.bool_])
def test_control_differs_on_every_type(dtype):
    x = random_image(dtype, (40, 50), 11)
    if dtype == np.bool_:  # blocks, so that opening and closing leave something
        x = np.repeat(np.repeat(x[:10, :13], 4, 0), 4, 1)[:40, :50]
    for kind in ({"op": "erode", "se": [3, 3]}, {"op": "closing", "se": [5, 5]},
                 {"op": "gradient", "se": [3, 3]}):
        assert reference.mismatches(reference.control(x, kind, {}),
                                    reference.expected(x, kind, {})) > 0, kind


# ------------------------------------------------------------------- stamps
def test_stamp_on_integers_writes_eight_pixels():
    base = frames(60, 80, 1, 3)[0]
    for index in (0, 7, 2**40 + 3):
        old = base.copy()  # the stamp as it has always been on 8-bit pixels
        old[index % 60, :8] = np.frombuffer(int(index).to_bytes(8, "little"), np.uint8)
        np.testing.assert_array_equal(traffic.stamp(base, index), old)
    wide = random_image(np.uint16, (30, 40), 2)
    got = traffic.stamp(wide, 2**33 + 0x0102)
    np.testing.assert_array_equal(got[(2**33 + 0x0102) % 30, :8], [2, 1, 0, 0, 2, 0, 0, 0])
    assert got.dtype == np.uint16 and np.count_nonzero(got != wide) <= 8


@pytest.mark.parametrize("shape", [(60, 80), (40, 24)])  # a row wider and narrower than 64
def test_stamp_is_unique_on_bool(shape):
    base = random_image(np.bool_, shape, 4)
    seen = set()
    for index in range(1000):
        img = traffic.stamp(base, index)
        assert img.dtype == np.bool_ and img.shape == base.shape
        assert np.count_nonzero(img != base) <= 64
        seen.add(img.tobytes())
    assert len(seen) == 1000
    row = 5 * shape[0] + 3  # row 3; its 64 bits, running on into row 4 where narrower
    flat = traffic.stamp(base, row).reshape(-1)
    bits = np.unpackbits(np.frombuffer(row.to_bytes(8, "little"), np.uint8), bitorder="little")
    np.testing.assert_array_equal(flat[3 * shape[1]:3 * shape[1] + 64], bits.astype(bool))
