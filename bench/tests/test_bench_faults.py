"""A whole run of each cell's kind, at a size a test can hold, with the
timed path broken underneath: the harness has to report ``correct`` false
for each fault the cell can have, and true with no fault.

The harness's look for a chip is skipped (``run_cell`` is driven
directly, on the CPU, with Pallas in interpret mode).
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run  # noqa: E402

PAIRS = [("rmat20", "bfs_roots"), ("rmat20", "pagerank_fixed"),
         ("er20", "pagerank_fixed"), ("er20", "bfs_roots")]


def tiny(config: str, traffic: str) -> dict:
    """A configuration's graph, partition and engine under a traffic mix,
    at scale 8 on 4 shards."""
    res = {"config": run.load_json(os.path.join(
               ROOT, "bench", "configs", f"{config}.json")),
           "traffic": run.load_json(os.path.join(
               ROOT, "bench", "traffic", f"{traffic}.json")),
           "end_to_end": [], "per_layer": []}
    res["config"]["graph"]["scale"] = 8
    res["config"]["partition"]["num_shards"] = 4
    return res


def run_tiny(config: str, traffic: str) -> dict:
    import jax
    return run.run_cell(tiny(config, traffic), 2**31 + 99, 0.0, False,
                        jax.devices()[:1], {"hbm_bytes_per_s": 819e9},
                        time.perf_counter())


def state_unchanged(mp, app):
    """Every round returns the state it was given."""
    import jax.numpy as jnp
    from repro import exchange
    if app == "bfs":
        real = exchange.fixpoint_round_stacked

        def round_fn(*a, **k):
            val, chg, mc = real(*a, **k)
            return a[5], jnp.zeros_like(chg), mc
        mp.setattr(exchange, "fixpoint_round_stacked", round_fn)
    else:
        real = exchange.pagerank_round_stacked

        def round_fn(*a, **k):
            _, counts = real(*a, **k)
            return a[7], counts
        mp.setattr(exchange, "pagerank_round_stacked", round_fn)


def half_dropped(mp, app):
    """Half of the edges' messages are left out."""
    from repro.core import engine
    real = engine.DeviceArrays.from_partition

    def from_partition(part):
        arrays = real(part)
        keep = np.arange(arrays.edge_mask.shape[1]) % 2 == 0
        return arrays._replace(edge_mask=arrays.edge_mask & keep)
    mp.setattr(engine.DeviceArrays, "from_partition",
               staticmethod(from_partition))


def exchange_skipped(mp, app):
    """The rhizome collapse, the exchange between a vertex's replicas on
    the stacked shards, is left out."""
    from repro.exchange import rounds
    mp.setattr(rounds, "stacked_collapse",
               lambda sem, arrays, cfg, table: table)


def answer_altered(mp, app):
    """One vertex's answer is altered where it is produced."""
    from repro.core import engine
    real = engine.vertex_values

    def vertex_values(part, val):
        out = np.array(real(part, val))
        fin = np.isfinite(out)
        i = int(np.flatnonzero(fin)[np.argmax(out[fin])])
        out[i] = out[i] + 1.0 if app == "bfs" else out[i] * 1.001
        return out
    mp.setattr(engine, "vertex_values", vertex_values)


FAULTS = [state_unchanged, half_dropped, exchange_skipped, answer_altered]
CASES = [(c, t, f) for c, t in PAIRS for f in FAULTS]


@pytest.mark.parametrize("config,traffic", PAIRS)
def test_sound_run_is_correct(config, traffic):
    out = run_tiny(config, traffic)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize(
    "config,traffic,fault", CASES,
    ids=[f"{c}-{t}-{f.__name__}" for c, t, f in CASES])
def test_fault_is_not_correct(config, traffic, fault, monkeypatch):
    app = run.load_json(os.path.join(
        ROOT, "bench", "traffic", f"{traffic}.json"))["app"]
    fault(monkeypatch, app)
    out = run_tiny(config, traffic)
    assert out["correct"] is False, out["checks"]
    assert out["failed"] >= 1


@pytest.mark.parametrize("section", ["engine", "partition"])
def test_setting_the_program_lacks_is_refused(section, capsys):
    import jax
    res = tiny("rmat20", "pagerank_fixed")
    res["config"][section]["no_such_setting"] = 1
    with pytest.raises(SystemExit) as e:
        run.run_cell(res, 5, 0.0, False, jax.devices()[:1],
                     {"hbm_bytes_per_s": 819e9}, time.perf_counter())
    assert e.value.code != 0
    assert capsys.readouterr().out == ""
