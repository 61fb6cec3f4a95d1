#!/usr/bin/env python3
"""Controls for the comparison that decides ``correct``: the reference,
put in the program's place with one step a later change might be tempted
to take, has to come out as not correct.  The benchmark's own runs never
run this; it is run by hand, on the chip at a cell's own size:

    python3 bench/control.py --workload rmat20.pagerank --seeds 11 12 13

Controls, by the traffic's app:

* ``pagerank``: the power iteration computed in bfloat16 on the device,
  the precision below the float32 that the configuration states.
* ``bfs``: the same search in bfloat16 on the device (levels are small
  integers, exact in bfloat16, so it reads no mismatch and cannot be the
  control), and a search stopped one level short of its deepest level, which
  breaks the configuration's guarantee of exact levels.

Each control's answers go through the cell's own driver ``verify``, and
every number is printed beside its limit, one line per seed and control.
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


def pagerank_device(n, src, dst, damping, iters, dtype):
    """The reference's power iteration as jnp on the default device, in
    ``dtype`` throughout."""
    import jax
    import jax.numpy as jnp
    w = jnp.asarray(reference.edge_weights(n, src), dtype)
    s, d = jnp.asarray(src), jnp.asarray(dst)

    @jax.jit
    def go(w):
        base = jnp.asarray((1.0 - damping) / n, dtype)

        def body(_, score):
            inc = jax.ops.segment_sum(score[s] * w, d, num_segments=n)
            return (base + jnp.asarray(damping, dtype) * inc).astype(dtype)
        return jax.lax.fori_loop(0, iters, body, jnp.full(n, 1.0 / n, dtype))
    return np.asarray(go(w).astype(jnp.float32), np.float64)


def bfs_device(n, src, dst, root, dtype):
    """Level-synchronous BFS as jnp min-relaxation on the default device,
    levels held in ``dtype``; unreached vertices get ``UNREACHED``."""
    import jax
    import jax.numpy as jnp
    s, d = jnp.asarray(src), jnp.asarray(dst)

    @jax.jit
    def go():
        inf = jnp.asarray(jnp.inf, dtype)
        level = jnp.full(n, inf).at[root].set(0)

        def body(carry):
            level, _ = carry
            cand = jax.ops.segment_min(level[s] + 1, d, num_segments=n)
            new = jnp.minimum(level, cand.astype(dtype))
            return new, jnp.any(new != level)
        return jax.lax.while_loop(lambda c: c[1], body, (level, True))[0]
    lv = np.asarray(go().astype(jnp.float32))
    out = np.full(n, reference.UNREACHED, np.int64)
    fin = np.isfinite(lv)
    out[fin] = lv[fin].astype(np.int64)
    return out


def controls(app: str, driver, n, src, dst, calls: int):
    """{control name: the answers it puts in the program's place}."""
    import jax.numpy as jnp
    traffic = driver.traffic
    if app == "pagerank":
        got = pagerank_device(n, src, dst, traffic["damping"],
                              traffic["iters"], jnp.bfloat16)
        return {"bfloat16": [got] * calls}
    if app == "bfs":
        indptr, indices = graphs.csr(n, src, dst)
        roots = [int(driver.roots[i % len(driver.roots)])
                 for i in range(calls)]
        short = []
        for r in roots:
            full = reference.bfs_levels(n, indptr, indices, r)
            short.append((r, reference.bfs_levels(
                n, indptr, indices, r,
                max_rounds=reference.bfs_rounds(full) - 2)))
        return {"bfloat16": [(r, bfs_device(n, src, dst, r, jnp.bfloat16))
                             for r in roots],
                "one_level_short": short}
    raise ValueError(f"no control for app {app!r}")


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
    app = res["traffic"]["app"]
    Driver = run.load_module("drivers", app).Driver
    for seed in args.seeds:
        n, src, dst = graphs.generate(res["config"]["graph"], seed)
        driver = Driver(res["traffic"], n, src, dst, None, None, seed)
        for name, answers in controls(app, driver, n, src, dst,
                                      args.calls).items():
            checks, failed, _ = driver.verify(answers)
            correct = failed == 0 and all(
                c["value"] <= c["limit"] for c in checks.values())
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "control": name, "correct": correct,
                              "failed": failed, "checks": checks}),
                  flush=True)


if __name__ == "__main__":
    main()
