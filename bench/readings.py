"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/readings.py --workload <cell> --seeds 11,12,13 \
        --seconds 10 --control 3 [--out bench/.out/readings.jsonl]

One process: the cell's seed-independent set-up once, then one run of its
traffic per seed at the cell's own size and load, each printed as one JSON
line with the numbers compared.  On the first ``--control`` seeds the run
also reads the control: the plain reference computed in the next lower
precision (bfloat16 for float32, int4 for int8) and compared in the
program's place.  The benchmark's own runs never do this.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time
import traceback

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    sys.path[:] = [os.path.join(ROOT, "src"), ROOT] + [
        p for p in sys.path if os.path.abspath(p or ".") != BENCH]
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, traffic, system_path = run.resolve(ROOT, bench,
                                                     args.workload)
    out_dir = os.path.join(BENCH, ".out", cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(out_dir, "tpu_logs"))
    import jax

    device = run.device_info(cell["chips"])
    if device["platform"] != "tpu" or device["count"] < cell["chips"]:
        print(f"readings: no TPU for {cell['name']}", file=sys.stderr)
        return 3
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    system = run.load_module(system_path, "bench_system_" + config["system"])
    seeds = [int(s) for s in args.seeds.split(",")]
    first = run.Context(cell, config, traffic, seeds[0], args.seconds,
                        False, out_dir)
    t0 = time.perf_counter()
    prep = system.prepare(first)
    print(json.dumps({"prepare_s": time.perf_counter() - t0}), flush=True)
    sink = open(args.out, "a") if args.out else None
    for i, seed in enumerate(seeds):
        ctx = run.Context(cell, config, traffic, seed, args.seconds, False,
                          out_dir)
        ctx.t_start = time.perf_counter()
        ctx.control = i < args.control
        try:
            res = system.serve(ctx, prep)
        except Exception:
            traceback.print_exc()
            continue
        row = {"cell": cell["name"], "seed": seed,
               "checks": {n: v for n, v, _lim in res["checks"]},
               "control": res.get("control"), "e2e": res["e2e"],
               "attempted": res["attempted"], "failed": res["failed"]}
        print(json.dumps(row), flush=True)
        if sink:
            sink.write(json.dumps(row) + "\n")
            sink.flush()
    if sink:
        sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
