"""The chip benchmark's generator and plain reference, on the CPU."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import reference, traffic  # noqa: E402

BIG_SEED = 2**40 + 12345  # the driver's seeds run past 32 bits


def test_same_seed_same_requests():
    for fn in (lambda s: traffic.kind_sequence(10, 500, s),
               lambda s: traffic.open_loop_due(400.0, 10.0, s),
               lambda s: traffic.smooth_frames(60, 80, 3, s),
               lambda s: traffic.scanned_pages(400, 300, 2, s)):
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
    base = traffic.smooth_frames(60, 80, 1, 3)[0]
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
    assert g.dtype == np.int16 and g.min() >= 0
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
    x = traffic.smooth_frames(60, 80, 1, 4)[0]
    kind = {"op": "erode", "se": [15, 15]}
    assert reference.mismatches(reference.control(x, kind, {}),
                                reference.expected(x, kind, {})) > 0
