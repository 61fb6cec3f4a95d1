#!/usr/bin/env python3
"""Chip benchmark of the graph engine: one cell of ``BENCHMARK.json`` per
run.

    python3 bench/run.py --workload rmat20.pagerank --seed 7 \
        --seconds 10 --trace 0

A cell names a configuration (``bench/configs/<config>.json``: the graph,
its partition and the engine settings) and a traffic mix
(``bench/traffic/<traffic>.json``, driven by ``bench/drivers/<app>.py``).
Every metric is computed by its own reader, ``bench/metrics/<name>.py``.

A run makes the graph from ``--seed``, builds the partition, warms up the
cell's calls (all of that is set-up), then starts calls one after another
for ``--seconds`` seconds; the window closes when the last call started
ends.  After the window every answer is compared with the benchmark's own
reference, and the numbers compared are printed beside their limits as
the last lines of standard error and under ``checks`` in the result.
With ``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result holds its
per-layer metrics, the device's busy time and a breakdown.

The last line of standard output is one JSON object.  Without a TPU, with
fewer devices than the cell asks for, with a device kind missing from
``bench/peaks.json``, or without the program's ``src/`` beside ``bench/``,
the run exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import compiles, devtrace, graphs, system  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_MAX_ENV = "JAX_COMPILATION_CACHE_MAX_SIZE"
CACHE_MAX_BYTES = 192 << 20


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def fail(msg: str) -> None:
    log(f"bench: {msg}")
    sys.exit(1)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(bench: dict, workload: str, root: str = ROOT) -> dict:
    """Everything one cell needs, found by the names in ``bench``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {
        "cell": cell,
        "config": load_json(os.path.join(root, config["file"])),
        "traffic": load_json(os.path.join(HERE, "traffic",
                                          f"{cell['traffic']}.json")),
        "end_to_end": [m for m in bench["end_to_end"]
                       if applies(m, workload)],
        "per_layer": [m for m in bench["per_layer"] if applies(m, workload)],
    }


def device_peak(devices, chips: int, peaks: dict) -> dict:
    """The peak table's entry for these devices; exits where the platform
    is not a TPU, the devices are too few, or the kind is unknown."""
    platform = devices[0].platform
    if platform != "tpu":
        fail(f"needs a TPU, but JAX found platform {platform!r} "
             f"({len(devices)} device(s)); nothing was run")
    if len(devices) < chips:
        fail(f"the cell needs {chips} chips; JAX found {len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks:
        fail(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path: the directory
    that ``JAX_COMPILATION_CACHE_DIR`` names, or ``<checkout>/.jax_cache``.

    Unless ``JAX_COMPILATION_CACHE_MAX_SIZE`` says otherwise, the cache
    holds at most ``CACHE_MAX_BYTES``, and JAX does not store a program
    larger than that.  The host loop's round programs embed the
    partition's tables (over 200 MB each at scale 20) and a graph made
    from another seed never finds them again; kept out, they are not
    written to disk in every run, and a run compiles the same whether or
    not its seed ran before in this checkout."""
    import jax
    path = os.environ.get(CACHE_ENV) or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if CACHE_MAX_ENV not in os.environ:
        jax.config.update("jax_compilation_cache_max_size", CACHE_MAX_BYTES)
    return path


def dir_stats(path: str) -> tuple[int, int]:
    """(files, bytes) under ``path``."""
    files = size = 0
    for dirpath, _, names in os.walk(path):
        for name in names:
            files += 1
            size += os.path.getsize(os.path.join(dirpath, name))
    return files, size


_WATCH = None


def compile_watch() -> compiles.CompileWatch:
    global _WATCH
    if _WATCH is None:
        _WATCH = compiles.CompileWatch()
    return _WATCH


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(res: dict, seed: int, seconds: float, trace: bool,
             devices, peak: dict, t_start: float) -> dict:
    """Set up, measure and check one cell; returns the result object."""
    import jax
    watch = compile_watch()
    watch.reset()
    config, traffic = res["config"], res["traffic"]
    t = time.perf_counter()
    n, src, dst = graphs.generate(config["graph"], seed)
    gen_s = time.perf_counter() - t
    ecfg, e_dropped = system.engine_config(config["engine"])
    pcfg, p_dropped = system.partition_config(config["partition"])
    if e_dropped or p_dropped:
        fail(f"settings {e_dropped + p_dropped} of the configuration are "
             "not fields of the program; the cell would run another path "
             "than its configuration states")
    driver = load_module("drivers", traffic["app"]).Driver(
        traffic, n, src, dst, pcfg, ecfg, seed)
    t = time.perf_counter()
    driver.build()
    partition_s = time.perf_counter() - t
    t = time.perf_counter()
    driver.warm()
    warm_s = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    log(f"graph {config['graph']['kind']} n={n} edges={src.size} "
        f"gen_s={gen_s!r} partition_s={partition_s!r} warm_s={warm_s!r} "
        f"setup_s={setup_s!r}")

    watch.phase = "window"
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    answers = []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with jax.profiler.TraceAnnotation("bench.call"):
                answers.append(driver.call(len(answers)))
            if time.perf_counter() >= deadline:
                break
    window_s = time.perf_counter() - t0
    traced = None
    if trace:
        jax.profiler.stop_trace()
        traced = devtrace.read(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        for line in traced.pop("planes"):
            log(f"trace plane {line}")
    mem = memory_peak(devices)

    watch.phase = "check"
    checks, failed, work = driver.verify(answers)
    for phase in ("setup", "window"):
        log(f"compiles {phase}: {watch.summary(phase)}")
    log(f"window calls={len(answers)} window_s={window_s!r} work={work}")
    ctx = {"setup": {"setup_s": setup_s, "partition_s": partition_s,
                     "gen_s": gen_s, "warm_s": warm_s},
           "window": {"seconds": window_s, "calls": len(answers),
                      **watch.summary("window")},
           "work": work, "trace": traced, "peak": peak}
    wanted = res["per_layer"] if trace else res["end_to_end"]
    metrics = {}
    for m in wanted:
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct = bool(answers) and failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": len(answers), "failed": failed,
           "metrics": metrics, "device": device}
    if traced is not None:
        device.update(busy_s=traced["busy_s"], window_s=traced["window_s"])
        out["breakdown"] = traced["breakdown"]
    out["checks"] = checks
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        res = resolve(load_json(os.path.join(ROOT, "BENCHMARK.json")),
                      args.workload)
    except (OSError, KeyError, ValueError) as e:
        fail(f"cannot resolve workload {args.workload!r}: {e!r}")
    peaks = load_json(os.path.join(HERE, "peaks.json"))["devices"]

    import jax
    devices = jax.devices()
    peak = device_peak(devices, res["cell"]["chips"], peaks)
    devices = devices[:res["cell"]["chips"]]
    src_dir = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src_dir, "repro")):
        fail(f"the program is not beside the benchmark ({src_dir})")
    sys.path.insert(0, src_dir)
    cache = enable_compile_cache()
    files, size = dir_stats(cache)
    log(f"device platform={devices[0].platform} "
        f"kind={devices[0].device_kind} count={len(jax.devices())} "
        f"compile_cache={cache} entries_at_start={files} "
        f"bytes_at_start={size}")
    out = run_cell(res, args.seed, args.seconds, bool(args.trace), devices,
                   peak, T_START)
    files, size = dir_stats(cache)
    log(f"compile_cache entries_at_end={files} bytes_at_end={size}")
    for name, c in out["checks"].items():
        log(f"check {name} value={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
