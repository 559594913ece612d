"""The harness: no result without a chip or outside a checkout, and cells,
configurations, traffic mixes and per-layer metrics found by name, so a new
one is new files and entries only, and the fleet's frozen weights checked
against the digest its configuration states."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def harness():
    spec = importlib.util.spec_from_file_location(
        "bench_run_under_test", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def bench_json(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cli(root, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"),
         "--workload", "fleet_busy", "--seed", str(2 ** 31 + 3), "--seconds",
         "1", "--trace", "0", *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)


def no_result_line(stdout):
    return not any(line.lstrip().startswith("{")
                   for line in stdout.splitlines())


def test_without_a_tpu_it_fails_and_prints_no_result():
    p = run_cli(ROOT)
    assert p.returncode != 0
    assert no_result_line(p.stdout)
    assert "TPU" in p.stderr


def test_outside_a_checkout_it_fails_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".out",
                                                  "__pycache__"))
    p = run_cli(str(tmp_path))
    assert p.returncode != 0
    assert no_result_line(p.stdout)


def test_every_cell_resolves_to_files_of_its_own():
    run = harness()
    bench = bench_json()
    for cell in bench["workloads"]:
        c, config, traffic, system = run.resolve(ROOT, bench, cell["name"])
        assert config["name"] == cell["config"]
        assert os.path.exists(system)
        assert run.end_to_end(bench, cell["name"])[0]["name"] == "setup_s"
        for m in run.per_layer(bench, cell["name"]):
            reader = run.load_module(run.reader_path(ROOT, m["name"]),
                                     "reader_" + m["name"].replace(".", "_"))
            assert callable(reader.read)


def test_a_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """Adding a cell takes new files and new entries, no edit of a file
    that is there."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".out",
                                                  "__pycache__"))
    bench = bench_json()
    cfg = json.load(open(tmp_path / "bench/configs/wispcam_fleet.json"))
    cfg["name"] = "wispcam_fleet_hd"
    (tmp_path / "bench/configs/wispcam_fleet_hd.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/traffic/rush.json").write_text(json.dumps(
        dict(json.load(open(tmp_path / "bench/traffic/busy.json")),
             streams=12)))
    (tmp_path / "bench/metrics/queue_depth.fleet.py").write_text(
        "def read(r):\n    return r.system['ticks'] * 2.0\n")
    bench["configs"].append({"name": "wispcam_fleet_hd", "source": "x",
                             "file": "bench/configs/wispcam_fleet_hd.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "fleet_rush",
                               "config": "wispcam_fleet_hd",
                               "traffic": "rush", "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:
        if "fleet_busy" in m.get("workloads", []):
            m["workloads"].append("fleet_rush")
    bench["per_layer"].append({"name": "queue_depth.fleet", "unit": "frames",
                               "better": "lower", "source": "program_span",
                               "layer": "x", "moves": "frame_p95_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    run = harness()
    cell, config, traffic, system = run.resolve(str(tmp_path), bench,
                                                "fleet_rush")
    assert config["name"] == "wispcam_fleet_hd" and traffic["streams"] == 12
    assert system.endswith(os.path.join("bench", "systems", "fleet.py"))
    names = [m["name"] for m in run.per_layer(bench, "fleet_rush")]
    # a metric with no list of cells is read wherever its end-to-end
    # metric is reported, and a listed one only in its cells
    assert "queue_depth.fleet" in names and "slot_fill.fleet" not in names
    assert "queue_depth.fleet" in [
        m["name"] for m in run.per_layer(bench, "fleet_busy")]
    assert "queue_depth.fleet" not in [
        m["name"] for m in run.per_layer(bench, "fleet_busy")
        if m["moves"] != "frame_p95_s"]
    reader = run.load_module(run.reader_path(str(tmp_path),
                                             "queue_depth.fleet"), "qd")
    assert reader.read(type("R", (), {"system": {"ticks": 3}})()) == 6.0


def fleet_system():
    return harness().load_module(os.path.join(BENCH, "systems", "fleet.py"),
                                 "fleet_under_test")


def test_the_deployment_weights_are_the_stated_ones():
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "wispcam_fleet.json")))
    casc, _net, plain_casc, plain_nn = fleet_system().load_detector(cfg)
    d = cfg["detector"]
    assert casc.stage_sizes == [d["per_stage"]] * d["stages"]
    assert plain_casc["stage_sizes"] == casc.stage_sizes
    assert [plain_nn[k].shape for k in ("w1", "w2")] == [
        tuple(cfg["nn"]["layers"][:2]), tuple(cfg["nn"]["layers"][1:])]


def test_weights_of_another_digest_are_refused():
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "wispcam_fleet.json")))
    cfg["weights"]["sha256"] = "0" * 64
    with pytest.raises(RuntimeError, match="sha256"):
        fleet_system().load_detector(cfg)
