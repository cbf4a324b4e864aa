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
    # a bare gradient is answered in int32: 1 byte read, 4 written
    assert harness.ideal_bytes({"op": "gradient", "se": [3, 3]}, px, 1, {}) == 5 * px
    assert harness.ideal_bytes({"op": "gradient", "se": [3, 3]}, px, 2, {}) == 6 * px
    cfg = harness.cell_spec(manifest(), "a4page.closed8", ROOT)["config"]
    page = 2480 * 3508
    assert harness.ideal_bytes({"plan": "document_cleanup"}, page, 1, cfg["plans"]) == 3 * page


def checkout_with(tmp_path, config: dict, traffic: dict, content: str | None = None):
    """A copy of the benchmark, with a configuration, a traffic mix and, where
    given, a content generator added as files, and a cell of them in its
    BENCHMARK.json: what a later PR that adds a cell brings."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    m = manifest()
    bench = root / "benchmarks" / "chip"
    name = config["name"]
    (bench / "configs" / f"{name}.json").write_text(json.dumps(config))
    (bench / "traffic" / "closed2.json").write_text(json.dumps(traffic))
    if content is not None:
        (bench / "content" / f"{config['image']['content']}.py").write_text(content)
    m["configs"].append({"name": name, "source": "https://example.org/tiny",
                         "file": f"benchmarks/chip/configs/{name}.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "tiny.closed2", "config": name,
                           "traffic": "closed2", "chips": 1, "why": "test"})
    return root, bench, m


def test_a_cell_added_from_files_alone(tmp_path):
    root, bench, m = checkout_with(tmp_path, {
        "name": "tiny_u8", "image": {"height": 40, "width": 60, "dtype": "uint8",
                                     "content": "smooth_frames"},
        "requests": [{"ops": ["dilate"], "se_sizes": [3]}],
        "service": {"max_batch": 2}},
        {"loop": "closed", "in_flight": 2, "image_pool": 2, "check_sample": 3})
    (bench / "metrics" / "answered.py").write_text(
        "def read(run):\n    return float(len(run.completed_in_window()))\n")
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


# Content generators of other pixel types, as a configuration's own file
# brings them; each reads a key of its own from the configuration's image.
U16_CONTENT = """import numpy as np

from chipbench.traffic import rng_for


def make(image, count, seed):
    h, w = int(image["height"]), int(image["width"])
    rng = rng_for(seed, "reflectance")
    x = rng.integers(0, int(image["reflectance_max"]) + 1, (count, h, w)).astype(np.uint16)
    x[:, ::7, ::5] = 65535  # saturated
    x[:, 3::11, ::3] = 0    # no data
    return x
"""

BOOL_CONTENT = """import numpy as np

from chipbench.traffic import rng_for


def make(image, count, seed):
    h, w = int(image["height"]), int(image["width"])
    rng = rng_for(seed, "masks")
    blocks = rng.random((count, h // 4 + 1, w // 4 + 1)) < float(image["density"])
    return np.repeat(np.repeat(blocks, 4, 1), 4, 2)[:, :h, :w]
"""

OTHER_TYPES = {  # case: (configuration, its content, tiled route)
    "uint16-tiled": ({
        "name": "tiny_u16", "image": {"height": 100, "width": 140, "dtype": "uint16",
                                      "content": "tiny_reflectance", "reflectance_max": 10000},
        "requests": [{"ops": ["opening", "closing"], "se_sizes": [3, 5]}],
        "service": {"buckets": [[64, 128]], "tile_interior": [32, 64],
                    "max_tiles_per_launch": 4}}, U16_CONTENT, True),
    "bool-bucketed": ({
        "name": "tiny_bool", "image": {"height": 40, "width": 60, "dtype": "bool",
                                       "content": "tiny_masks", "density": 0.4},
        "requests": [{"ops": ["erode", "dilate", "gradient"], "se_sizes": [3, 5]}],
        "service": {"max_batch": 2}}, BOOL_CONTENT, False),
}


@pytest.mark.parametrize("case", sorted(OTHER_TYPES))
def test_a_cell_of_another_pixel_type_added_from_files_alone(tmp_path, case):
    """A u16 cell on the tiled route and a bool cell on the bucketed route,
    each added as files alone (configuration, traffic, content): correct when
    served, and not correct when the control stands in for the answers."""
    config, content, tiled = OTHER_TYPES[case]
    root, bench, m = checkout_with(tmp_path, config, {"loop": "closed", "in_flight": 2,
                                                      "image_pool": 2, "check_sample": 6},
                                   content)
    (bench / "metrics" / "tiled_launches.py").write_text(
        "def read(run):\n    return run.counter_delta('tiled.launches')\n")
    m["end_to_end"].append({"name": "tiled_launches", "unit": "launches", "better": "lower",
                            "bound": 0.05, "source": "host_clock",
                            "workloads": ["tiny.closed2"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    spec = harness.cell_spec(harness.load_manifest(str(root)), "tiny.closed2", str(root))
    harness.import_program(ROOT)
    for control in (False, True):
        out = harness.run_cell(spec, 2**35 + 3, 0.6, False, t_start=time.perf_counter(),
                               require_tpu=False, control=control)
        assert out["failed"] == 0 and out["checks"]["compared_answers"]["value"] >= 1
        assert out["correct"] is not control, (control, out["checks"])
        assert (out["checks"]["wrong_pixels"]["value"] > 0) is control
        assert (out["metrics"]["tiled_launches"]["value"] > 0) is tiled


# ------------------------------------------------------- whole runs on the CPU
def small_spec(workload):
    spec = harness.cell_spec(manifest(), workload, ROOT)
    cfg = spec["config"]
    if "plans" in cfg:  # the page: the tiled route at a small size
        cfg["image"].update(height=150, width=200)
        cfg["service"] = {"buckets": [[64, 128]], "tile_interior": [64, 64],
                          "max_tiles_per_launch": 4}
    elif "replicas" in cfg:  # two replicas on the one CPU device, both in use:
        cfg["replicas"] = 2  # erode and dilate hash apart at the (128, 128) bucket
        cfg["image"].update(height=100, width=120)
        cfg["requests"][0]["se_sizes"] = [3, 15]
        cfg["service"] = {"max_batch": 4}
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


CELLS = ["paper.closed64", "a4page.closed8", "paper.poisson80", "paper4.closed256"]


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


# ------------------------------------------------- the layout over replicas
def replica_stack(traced=False):
    import jax

    harness.import_program(ROOT)
    cfg = small_spec("paper4.closed256")["config"]
    return cfg, harness.open_stack(cfg, traced, 4096, jax.devices()[:4])


def test_replicas_are_pinned_and_behind_one_frontier():
    import jax

    cfg, stack = replica_stack()
    try:
        assert [s.config.shard for s in stack.services] == [0, 1]
        assert all(s.config.device == jax.devices()[0] for s in stack.services)
        assert [link.address for link in stack.frontier.links] == [h.address
                                                                   for h in stack.hosts]
    finally:
        stack.close()
    cfg1 = small_spec("paper.closed64")["config"]
    stack = harness.open_stack(cfg1, False, 4096, jax.devices()[:1])
    try:  # one replica: the process's default device, no shard, as before
        (svc,) = stack.services
        assert svc.config.device is None and svc.config.shard is None
    finally:
        stack.close()


def test_counters_are_the_sum_over_replicas():
    from chipbench import traffic as gen

    cfg, stack = replica_stack()
    try:
        before = stack.frontier.metrics_snapshot()
        kinds = gen.request_kinds(cfg)
        imgs = gen.image_pool(cfg, 2, 5, BENCH)
        futs = [stack.client.submit_plan(imgs[i % 2], harness.server_plan(k))
                for i, k in enumerate(kinds * 2)]
        futs += [stack.services[1].submit_plan(imgs[0], harness.server_plan(kinds[0]))]
        for f in futs:
            f.result(timeout=120)
        snaps = [svc.metrics_snapshot() for svc in stack.services]
        each = [harness.counter(s, "requests") for s in snaps]
        assert all(n > 0 for n in each)  # both replicas served
        total = stack.frontier.metrics_snapshot()
        assert harness.counter(total, "requests") == sum(each) == 2 * len(kinds) + 1
        assert harness.counter(before, "requests") == 0
        px = [harness.counter(s, "executor.pixels_launched") for s in snaps]
        assert harness.counter(total, "executor.pixels_launched") == sum(px) > 0
        assert [w["requests"] for w in stack.frontier.stats()["per_worker"]] == each
    finally:
        stack.close()


def test_every_serving_replica_is_sampled():
    """The check keeps the lowest draws of each replica that answered: one
    answer from a replica that took almost nothing is compared, though a
    sample drawn over all answers would leave it out."""
    import types
    from concurrent.futures import Future

    from chipbench import traffic as gen
    from chipbench.spans import TRACE_BASE

    seed, n, sample = 2**33 + 5, 200, 4
    served_by = {}
    stack = types.SimpleNamespace(client=None, served_by=served_by)
    driver = harness.Driver(stack, [{}], [None], [], seed, sample)
    draws = {i: gen.check_priority(seed, i) for i in range(n)}
    rare = max(draws, key=draws.get)
    for i in range(n):
        served_by[TRACE_BASE + i] = 3 if i == rare else i % 2
        fut = Future()
        fut.set_result(i)
        driver._finished(harness.Record(i, 0, due=0.0), fut)
    kept = [i for i, _ in driver.kept()]
    assert rare in kept
    assert driver.kept_per_replica() == {0: sample, 1: sample, 3: 1}
    for r in (0, 1):
        own = sorted((i for i in range(n) if i != rare and i % 2 == r), key=draws.get)
        assert sorted(i for i in kept if i != rare and i % 2 == r) == sorted(own[:sample])


def test_one_snapshot_sums_to_itself():
    """One replica's counters read as they did before the sum."""
    harness.import_program(ROOT)
    from repro.serve.ingress.stats import merge_worker_metrics

    snap = {"requests": {"type": "counter", "value": 7},
            "batches": {"type": "counter", "value": 3}}
    assert merge_worker_metrics([snap]) == snap


def test_every_replica_warms_every_kind():
    from chipbench import traffic as gen

    cfg, stack = replica_stack()
    try:
        kinds = gen.request_kinds(cfg)
        warmed = harness.warm_executors(stack, kinds, cfg)
        batches = [1, 2, 4]  # every power of two up to max_batch 4
        assert warmed == 2 * len(kinds) * len(batches)
        for svc in stack.services:
            for kind in kinds:
                for b in batches:
                    key = svc._executor_key(harness.server_plan(kind), (128, 128),
                                            np.uint8, b)
                    assert key in svc.cache._entries
            assert svc.cache.misses == len(kinds) * len(batches)
    finally:
        stack.close()


def test_traced_spans_come_from_every_replica():
    from chipbench import traffic as gen

    cfg, stack = replica_stack(traced=True)
    try:
        kinds = gen.request_kinds(cfg)
        img = gen.image_pool(cfg, 1, 3, BENCH)[0]
        for f in [stack.client.submit_plan(img, harness.server_plan(k)) for k in kinds]:
            f.result(timeout=120)
        pids = {ev["pid"] for ev in stack.trace_events()
                if ev.get("name") == "dispatch" and ev.get("ph") == "X"}
        assert pids == {"0", "1"}
    finally:
        stack.close()


def test_more_replicas_than_chips_is_an_error():
    spec = small_spec("paper4.closed256")
    spec["config"]["replicas"] = int(spec["cell"]["chips"]) + 1
    harness.import_program(ROOT)
    with pytest.raises(SystemExit, match="replicas"):
        harness.run_cell(spec, 3, 0.2, False, t_start=time.perf_counter(),
                         require_tpu=False)


@pytest.fixture
def one_replica_altered(monkeypatch):
    """Replica 1's executors alone alter one pixel in the middle of each
    image where the answer is produced; replica 0 is sound."""
    from repro.serve.morph.service import MorphService

    real = MorphService._executor_for

    def executor_for(self, *a, **kw):
        fn = real(self, *a, **kw)
        if self.config.shard != 1:
            return fn

        def broken(x, rect):
            outs, aux = fn(x, rect)
            h, w = x.shape[1] // 2, x.shape[2] // 2
            return {k: v.at[:, h, w].add(1) for k, v in outs.items()}, aux
        return broken

    monkeypatch.setattr(MorphService, "_executor_for", executor_for)


def test_one_replica_altered_is_not_correct(one_replica_altered):
    """The check draws its sample from each replica's answers: with one
    answer kept a replica, replica 1's altered answer is among them."""
    spec = small_spec("paper4.closed256")
    spec["traffic"]["check_sample"] = 1
    harness.import_program(ROOT)
    out = harness.run_cell(spec, 2**33 + 78, 0.6, False, t_start=time.perf_counter(),
                           require_tpu=False)
    assert out["correct"] is False
    assert out["checks"]["wrong_pixels"]["value"] > 0


# ------------------------------------------------------- the process's environment
def test_a_configuration_states_its_process_environment():
    from chipbench import launch

    page = launch.stated_environment(["--workload", "a4page.closed8", "--seed", "1"], ROOT)
    assert page["GLIBC_TUNABLES"].startswith("glibc.malloc.arena_max=1:")
    assert launch.stated_environment(["--workload", "paper.closed64"], ROOT) == {}
    assert launch.stated_environment(["--workload", "no.such.cell"], ROOT) == {}
    assert launch.stated_environment([], ROOT) == {}


def test_relaunch_execs_only_where_a_stated_value_is_missing(monkeypatch):
    from chipbench import launch

    class Execed(Exception):
        pass

    def execve(path, args, env):
        raise Execed(path, args, env)

    monkeypatch.setattr(os, "execve", execve)
    argv = ["--workload", "a4page.closed8", "--seed", "3", "--seconds", "1"]
    stated = launch.stated_environment(argv, ROOT)
    monkeypatch.delenv("GLIBC_TUNABLES", raising=False)
    with pytest.raises(Execed) as ex:
        launch.relaunch(argv, ROOT, 12.5)
    path, args, env = ex.value.args
    assert path == sys.executable and args[-len(argv):] == argv
    assert env["GLIBC_TUNABLES"] == stated["GLIBC_TUNABLES"]
    assert float(env[launch.T_START_VAR]) == 12.5
    # the new image: the value is there, set-up counts from the first start
    monkeypatch.setenv("GLIBC_TUNABLES", stated["GLIBC_TUNABLES"])
    monkeypatch.setenv(launch.T_START_VAR, "12.5")
    assert launch.relaunch(argv, ROOT, 99.0) == 12.5
    assert launch.T_START_VAR not in os.environ
    # a cell that states nothing runs where it started
    assert launch.relaunch(["--workload", "paper.closed64"], ROOT, 7.0) == 7.0
