#!/usr/bin/env python3
"""Control for the comparison that decides ``correct`` in delta-PageRank
cells: the same push rounds as the program's, computed on the device in
bfloat16, the precision below the float32 that the configuration states,
have to come out as not correct; in float32 they have to come out as
correct.  The benchmark's own runs never run this; it is run by hand, on
the chip at a cell's own size:

    python3 bench/control_delta.py --workload rmat20.pagerank_delta \\
        --seeds 11 12 13

Each control's answer goes through the cell's own driver ``verify``, and
every number is printed beside its limit, one line per seed and
precision.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from bench import graphs, reference, run  # noqa: E402


def delta_device(n, src, dst, damping, tol, max_rounds, dtype):
    """``bench.reference_delta.delta_rounds`` as jnp on the default
    device, in ``dtype`` throughout; returns (ranks, rounds run)."""
    import jax
    import jax.numpy as jnp
    w = jnp.asarray(reference.edge_weights(n, src), dtype)
    s, d = jnp.asarray(src), jnp.asarray(dst)

    @jax.jit
    def go(w):
        t = jnp.asarray(tol, dtype)
        dmp = jnp.asarray(damping, dtype)
        base = jnp.full(n, (1.0 - damping) / n, dtype)

        def live(delta):
            return jnp.abs(delta) > t

        def body(carry):
            rank, delta, it = carry
            push = jnp.where(live(delta)[s], delta[s] * w,
                             jnp.zeros((), dtype))
            new = (dmp * jax.ops.segment_sum(push, d, num_segments=n)
                   ).astype(dtype)
            return (rank + new).astype(dtype), new, it + 1

        def cond(carry):
            _, delta, it = carry
            return jnp.any(live(delta)) & (it < max_rounds)
        return jax.lax.while_loop(cond, body, (base, base, 0))
    rank, _, rounds = go(w)
    return np.asarray(rank.astype(jnp.float32), np.float64), int(rounds)


def controls(driver, n, src, dst, calls: int):
    """{precision: the answers it puts in the program's place}."""
    import jax.numpy as jnp
    out = {}
    for name, dtype in (("bfloat16", jnp.bfloat16),
                        ("float32", jnp.float32)):
        out[name] = [delta_device(n, src, dst, driver.damping, driver.tol,
                                  driver.max_rounds, dtype)] * calls
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--calls", type=int, default=1,
                    help="answers per seed, as many as a run's window holds")
    args = ap.parse_args(argv)
    res = run.resolve(run.load_json(os.path.join(ROOT, "BENCHMARK.json")),
                      args.workload)
    import jax
    dev = jax.devices()[0]
    print(f"device platform={dev.platform} kind={dev.device_kind}",
          file=sys.stderr, flush=True)
    Driver = run.load_module("drivers", res["traffic"]["app"]).Driver
    for seed in args.seeds:
        n, src, dst = graphs.generate(res["config"]["graph"], seed)
        driver = Driver(res["traffic"], n, src, dst, None, None, seed)
        for name, answers in controls(driver, n, src, dst,
                                      args.calls).items():
            checks, failed, _ = driver.verify(answers)
            correct = failed == 0 and all(
                c["value"] <= c["limit"] for c in checks.values())
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "control": name, "rounds": answers[0][1],
                              "correct": correct, "failed": failed,
                              "checks": checks}), flush=True)


if __name__ == "__main__":
    main()
