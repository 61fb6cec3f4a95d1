"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Individual benches:
  python -m benchmarks.table1   (dataset statistics, Table 1)
  python -m benchmarks.fig6     (action/diffusion pruning, Fig 6)
  python -m benchmarks.fig7     (strong scaling, Fig 7)
  python -m benchmarks.fig8     (rpvo_max sweep, Fig 8)
  python -m benchmarks.fig9     (contention histogram, Fig 9)
  python -m benchmarks.fig10    (mesh vs torus, Fig 10)
  python -m benchmarks.kernelbench (Pallas kernel vs jnp oracle check)
  python -m benchmarks.roofline (LM+graph roofline table from the dry-run)

Exits non-zero when any module fails.
"""
import importlib
import sys
import time


MODULES = ["table1", "fig6", "fig7", "fig8", "fig9", "fig10", "kernelbench",
           "roofline"]


def main() -> int:
    print("name,us_per_call,derived")
    failed = []
    for name in MODULES:
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            mod.main()
            print(f"bench/{name},{(time.time()-t0)*1e6:.0f},ok")
        except Exception as e:  # noqa: BLE001
            print(f"bench/{name},{(time.time()-t0)*1e6:.0f},"
                  f"ERROR:{type(e).__name__}:{e}", file=sys.stderr)
            print(f"bench/{name},{(time.time()-t0)*1e6:.0f},error")
            failed.append(name)
    if failed:
        print(f"failed: {','.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == '__main__':
    sys.exit(main())
