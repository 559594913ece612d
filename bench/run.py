"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are looked up by name:
``BENCHMARK.json`` at the checkout's root names the cell's configuration
(``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<traffic>.json``); the configuration's ``system`` names
the module (``bench/systems/<system>.py``) that sets the deployment up,
offers the traffic, measures the window and checks what the timed path
produced against the plain reference.  With ``--trace 1`` the window is
traced and each per-layer metric is read by ``bench/metrics/<name>.py``.

One process holds the chip and starts no children.  Without a TPU, with
fewer chips than the cell asks for, or outside a checkout of the
repository, it exits non-zero and prints no result.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, ``breakdown`` (traced runs only) and
last ``checks``, each number compared beside its limit; the same numbers
are the last lines of standard error.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_module(path: str, name: str):
    """Import a file by path (metric readers have dots in their names)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_of(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def config_of(bench: dict, name: str) -> dict:
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            return cfg
    raise SystemExit(f"bench: no config {name!r} in BENCHMARK.json")


def resolve(root: str, bench: dict, name: str) -> tuple:
    """(cell, configuration, traffic mix, system module path) of a cell, each
    found by its name under ``root``."""
    cell = cell_of(bench, name)
    config = load_json(os.path.join(root, config_of(bench, cell["config"])
                                    ["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     cell["traffic"] + ".json"))
    system = os.path.join(root, "bench", "systems", config["system"] + ".py")
    return cell, config, traffic, system


def reader_path(root: str, metric: str) -> str:
    return os.path.join(root, "bench", "metrics", metric + ".py")


def end_to_end(bench: dict, cell: str) -> list:
    """The end-to-end metrics this cell reports."""
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer(bench: dict, cell: str) -> list:
    """The per-layer metrics read in this cell's traced run: those that
    list it, or, without a list, those whose end-to-end metric it
    reports."""
    moves = {m["name"] for m in end_to_end(bench, cell)}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in moves
                             else [])]


class Context:
    """What a system module gets: the cell's files, the run's arguments,
    the window's hook and the host-span annotation."""

    def __init__(self, cell, config, traffic, seed, seconds, trace, out_dir):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.control = False          # bench/readings.py turns it on
        self.t_start = T_START
        self.trace_dir = os.path.join(out_dir, "trace")
        self._profiling = False
        self._window_span = None

    def window(self, opening: bool):
        """Called by the system module as its window opens and closes."""
        import jax

        if opening:
            if self.trace:
                shutil.rmtree(self.trace_dir, ignore_errors=True)
                from bench import devtrace

                jax.profiler.start_trace(
                    self.trace_dir,
                    profiler_options=devtrace.profile_options())
                self._profiling = True
                self._window_span = jax.profiler.TraceAnnotation(
                    "bench.window")
                self._window_span.__enter__()
        elif self._profiling:
            self._window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._profiling = False

    def memory_peak_bytes(self) -> int:
        """Peak device memory on the fullest chip the cell uses."""
        import jax

        return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in jax.devices()[:self.cell["chips"]]))

    def annotate(self, label: str):
        if not self._profiling:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench." + label)


class Reading:
    """What a per-layer metric reader gets from a traced run."""

    def __init__(self, tr, w0, w1, system, peaks):
        self.trace, self.w0, self.w1 = tr, w0, w1
        self.devices = tr["devices"]
        self.host = tr["host"]
        self.system = system          # numbers the system module counted
        self.peaks = peaks

    def ops(self):
        """Every device's operations, one list."""
        return [o for ops in self.devices.values() for o in ops]

    def busy_s(self) -> float:
        """Device busy seconds in the window, averaged over the chips."""
        from bench import devtrace as trace

        if not self.devices:
            return 0.0
        return sum(trace.busy_ns(ops, self.w0, self.w1)
                   for ops in self.devices.values()) / len(self.devices) * 1e-9

    def kernel_s(self, kernel: str) -> tuple:
        """(seconds, events) of one of ``devtrace.KERNELS``."""
        from bench import devtrace as trace

        ns, n = trace.kernel_ns(self.ops(), kernel, self.w0, self.w1)
        return ns * 1e-9, n


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def format_checks(checks) -> dict:
    return {name: {"value": value, "limit": limit}
            for name, value, limit in checks}


def run(args) -> int:
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"bench: no program beside {BENCH} (src/repro is missing)",
              file=sys.stderr)
        return 2
    # the script's own directory first on the path would let bench/*.py
    # shadow modules of the standard library
    sys.path[:] = [os.path.join(ROOT, "src"), ROOT] + [
        p for p in sys.path if os.path.abspath(p or ".") != BENCH]
    cell, config, traffic, _system = resolve(ROOT, bench, args.workload)
    out_dir = os.path.join(BENCH, ".out", cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    # libtpu writes its logs under TPU_LOG_DIR, by default a fixed path
    # outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(out_dir, "tpu_logs"))

    import jax

    device = device_info(cell["chips"])
    if device["platform"] != "tpu" or device["count"] < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX sees {jax.devices()}", file=sys.stderr)
        return 3
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    line = execute(bench, cell, config, traffic, args.seed, args.seconds,
                   bool(args.trace), device, out_dir)
    print(json.dumps(line), flush=True)
    return 0


def execute(bench, cell, config, traffic, seed, seconds, traced, device,
            out_dir) -> dict:
    """Run the cell on ``device`` and return its result line; the checks
    go to standard error."""
    from bench import costs, devtrace as trace

    system = load_module(os.path.join(BENCH, "systems",
                                      config["system"] + ".py"),
                         "bench_system_" + config["system"])
    ctx = Context(cell, config, traffic, seed, seconds, traced, out_dir)
    res = system.run(ctx)
    device = dict(device, memory_peak_bytes=res["memory_peak_bytes"])

    metrics = {}
    breakdown = None
    if traced:
        tr = trace.extract(ctx.trace_dir)
        w0, w1 = trace.window(tr)
        reading = Reading(tr, w0, w1, res["reading"],
                          costs.peaks(device["kind"]))
        device["busy_s"] = reading.busy_s()
        device["window_s"] = (w1 - w0) * 1e-9
        for m in per_layer(bench, cell["name"]):
            reader = load_module(reader_path(ROOT, m["name"]),
                                 "bench_metric_" + m["name"].replace(".", "_"))
            got = reader.read(reading)
            if got is None:
                continue
            value, extra = got if isinstance(got, tuple) else (got, {})
            metrics[m["name"]] = {"value": value, "unit": m["unit"], **extra}
        ops = reading.ops()
        breakdown = {"device_ops": trace.top_ops(ops, w0, w1),
                     "idle_gaps": trace.top_gaps(
                         ops, tr["host"], w0, w1)}
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    else:
        for m in end_to_end(bench, cell["name"]):
            metrics[m["name"]] = {"value": res["e2e"][m["name"]],
                                  "unit": m["unit"]}

    checks = res["checks"]
    correct = all(v == v and v <= lim for _n, v, lim in checks)
    for name, value, limit in checks:
        print(f"check {name} = {value!r} (limit {limit!r})", file=sys.stderr,
              flush=True)
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = format_checks(checks)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return run(args)
    except Exception:
        traceback.print_exc()
        return 1


if __name__ == "__main__":
    sys.exit(main())
