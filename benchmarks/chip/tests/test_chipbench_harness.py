"""The chip benchmark's harness on the CPU, at sizes a test run holds: cells
found by name, one added from files alone, ideal bytes, and whole runs of the
served path (the look for a chip skipped) that must come out correct when the
path is sound and not correct when it is broken underneath."""
import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

from chipbench import harness  # noqa: E402


def manifest():
    return harness.load_manifest(ROOT)


def test_every_cell_resolves_by_name():
    m = manifest()
    for w in m["workloads"]:
        spec = harness.cell_spec(m, w["name"], ROOT)
        assert spec["config"]["name"] == w["config"]
        assert spec["traffic"]["loop"] in ("open", "closed")
        for metric in spec["end_to_end"] + spec["per_layer"]:
            assert callable(harness.load_reader(metric["name"], spec["bench_dir"]))
        names = {x["name"] for x in spec["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert spec["per_layer"]


def test_per_layer_metrics_list_only_cells_that_report_what_they_move():
    m = manifest()
    e2e = {x["name"]: x for x in m["end_to_end"]}
    cells = {w["name"] for w in m["workloads"]}
    for metric in m["per_layer"]:
        moved = e2e[metric["moves"]]
        for cell in metric.get("workloads", cells):
            assert cell in cells
            assert "workloads" not in moved or cell in moved["workloads"], (metric, cell)


def test_unknown_cell_and_unknown_device_are_errors():
    with pytest.raises(SystemExit):
        harness.cell_spec(manifest(), "no.such.cell", ROOT)
    with pytest.raises(SystemExit):
        harness.peak_for("TPU v99", BENCH)
    assert harness.peak_for("TPU v5 lite", BENCH)["hbm_bytes_per_s"] == 819e9


def test_ideal_bytes_count_the_request_not_the_bucket():
    px = 800 * 600
    assert harness.ideal_bytes({"op": "erode", "se": [3, 3]}, px, 1, {}) == 2 * px
    assert harness.ideal_bytes({"op": "gradient", "se": [3, 3]}, px, 1, {}) == 3 * px
    cfg = harness.cell_spec(manifest(), "a4page.closed8", ROOT)["config"]
    page = 2480 * 3508
    assert harness.ideal_bytes({"plan": "document_cleanup"}, page, 1, cfg["plans"]) == 3 * page


def test_a_cell_added_from_files_alone(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    m = manifest()
    bench = root / "benchmarks" / "chip"
    (bench / "configs" / "tiny_u8.json").write_text(json.dumps({
        "name": "tiny_u8", "image": {"height": 40, "width": 60, "dtype": "uint8",
                                     "content": "smooth_frames"},
        "requests": [{"ops": ["dilate"], "se_sizes": [3]}],
        "service": {"max_batch": 2}}))
    (bench / "traffic" / "closed2.json").write_text(json.dumps(
        {"loop": "closed", "in_flight": 2, "image_pool": 2, "check_sample": 3}))
    (bench / "metrics" / "answered.py").write_text(
        "def read(run):\n    return float(len(run.completed_in_window()))\n")
    m["configs"].append({"name": "tiny_u8", "source": "https://example.org/tiny",
                         "file": "benchmarks/chip/configs/tiny_u8.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "tiny.closed2", "config": "tiny_u8",
                           "traffic": "closed2", "chips": 1, "why": "test"})
    m["end_to_end"].append({"name": "answered", "unit": "images", "better": "higher",
                            "bound": 0.05, "source": "host_clock",
                            "workloads": ["tiny.closed2"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    spec = harness.cell_spec(harness.load_manifest(str(root)), "tiny.closed2", str(root))
    assert [x["name"] for x in spec["end_to_end"]] == ["setup_s", "answered"]
    harness.import_program(ROOT)
    out = harness.run_cell(spec, 2**35 + 1, 0.5, False, t_start=time.perf_counter(),
                           require_tpu=False)
    assert out["correct"] is True
    assert out["metrics"]["answered"]["value"] > 0
    assert set(out["metrics"]) == {"setup_s", "answered"}


# ------------------------------------------------------- whole runs on the CPU
def small_spec(workload):
    spec = harness.cell_spec(manifest(), workload, ROOT)
    cfg = spec["config"]
    if "plans" in cfg:  # the page: the tiled route at a small size
        cfg["image"].update(height=150, width=200)
        cfg["service"] = {"buckets": [[64, 128]], "tile_interior": [64, 64],
                          "max_tiles_per_launch": 4}
    else:
        cfg["image"].update(height=60, width=80)
        cfg["requests"][0]["se_sizes"] = [3, 15]
        cfg["service"] = {"max_batch": 4}
    spec["traffic"]["in_flight"] = 4
    spec["traffic"]["image_pool"] = 3
    spec["traffic"]["check_sample"] = 12
    return spec


def run_small(workload, **kw):
    harness.import_program(ROOT)
    return harness.run_cell(small_spec(workload), 2**33 + 77, 0.6, False,
                            t_start=time.perf_counter(), require_tpu=False, **kw)


CELLS = ["paper.closed64", "a4page.closed8", "paper.poisson80"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    out = run_small(workload)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["compared_answers"]["value"] >= 1
    assert set(out["metrics"]) == {m["name"] for m in
                                   harness.cell_spec(manifest(), workload, ROOT)["end_to_end"]}


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    out = run_small(workload, control=True)
    assert out["correct"] is False
    assert out["checks"]["wrong_pixels"]["value"] > 0


@pytest.fixture
def altered_answers(monkeypatch):
    """Every executor's answer altered where it is produced: one pixel in the
    middle of each image or tile."""
    from repro.serve.morph import service

    real = service.build_executor

    def build(*a, **kw):
        fn = real(*a, **kw)

        def broken(x, rect):
            outs, aux = fn(x, rect)
            h, w = x.shape[1] // 2, x.shape[2] // 2
            return {k: v.at[:, h, w].add(1) for k, v in outs.items()}, aux
        return broken

    monkeypatch.setattr(service, "build_executor", build)


@pytest.mark.parametrize("workload", CELLS)
def test_altered_answer_is_not_correct(workload, altered_answers):
    out = run_small(workload)
    assert out["correct"] is False
    assert out["checks"]["wrong_pixels"]["value"] > 0


@pytest.fixture
def half_batch_left_out(monkeypatch):
    """Each batched dispatch runs only its first half, rounded down; the rest
    are answered with their own input, unprocessed."""
    from repro.serve.morph.service import MorphService

    real = MorphService._execute_bucketed

    def half(self, key, reqs):
        keep = len(reqs) // 2
        for r in reqs[keep:]:
            if not r.future.done():
                r.future.set_result(np.array(r.img))
        if keep:
            real(self, key, reqs[:keep])

    monkeypatch.setattr(MorphService, "_execute_bucketed", half)


def test_half_batch_left_out_is_not_correct(half_batch_left_out):
    out = run_small("paper.closed64")
    assert out["correct"] is False
    assert out["checks"]["wrong_pixels"]["value"] > 0


@pytest.fixture
def dropped_answers(monkeypatch):
    """Every other request of the window (those carrying the benchmark's trace
    ids) fails inside the service instead of being served."""
    from repro.serve.morph.service import MorphService

    real = MorphService._execute_group
    count = [0]

    def drop(self, key, reqs):
        keep = []
        for r in reqs:
            count[0] += r.trace is not None
            if r.trace is None or count[0] % 2:
                keep.append(r)
            elif not r.future.done():
                r.future.set_exception(RuntimeError("dropped"))
        if keep:
            real(self, key, keep)

    monkeypatch.setattr(MorphService, "_execute_group", drop)


@pytest.mark.parametrize("workload", CELLS)
def test_failed_answers_are_not_correct(workload, dropped_answers):
    out = run_small(workload)
    assert out["correct"] is False
    assert out["failed"] > 0
    assert out["checks"]["failed_requests"]["value"] == out["failed"]
