"""The exact alias draw on the heavy-tailed Reddit graph, read outside the
benchmark's timed window (PERF.md section 5, cell
``reddit_powerlaw_device_train``):

    python scripts/powerlaw_alias_study.py [--hlo <train_step.hlo.txt>]
        [--draws 1000000] [--out chiprun_out/alias_study.json]

1. the degree law as generated (the graph function's ``degree_law()``);
2. for the 20 longest rows and 20 rows shorter than 10, the total-variation
   distance between ``--draws`` draws of the step's own draw
   (``device.sample_neighbor`` over the alias tables the cell's model
   builds through ``run_loop.build_model``, placed as the harness places
   them) and the exact pmf from the graph function; beside it what
   sampling alone gives (``noise_tvd``: its mean and spread), the bound
   the CPU test holds (the mean and ``BOUND_SDS`` spreads) and what a
   uniform draw over the row's slots would read;
3. the gathers of a compiled step's HLO text (``--hlo``: the traced run's
   ``train_step.hlo.txt``) whose operands are the alias tables, with
   their layouts.

Prints one JSON object, and writes it to ``--out``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "reddit_powerlaw_device_train"
# a draw passes where its distance from the exact pmf is under what
# sampling alone gives on average and this many of its standard
# deviations (tests/benchmark/test_powerlaw_alias.py holds the same)
BOUND_SDS = 6.0


def exact_pmf(spec, node: int) -> dict:
    """id -> probability of a draw from ``node`` in proportion to weight,
    over its whole list (an id listed twice takes both weights)."""
    _, ids, w = spec.neighbours([node])
    uniq, at = np.unique(ids, return_inverse=True)
    mass = np.bincount(at, weights=w.astype(np.float64))
    return dict(zip(uniq.tolist(), (mass / mass.sum()).tolist()))


def slot_pmf(spec, node: int) -> dict:
    """The pmf of a uniform draw over the row's slots: the fault."""
    _, ids, _ = spec.neighbours([node])
    uniq, counts = np.unique(ids, return_counts=True)
    return dict(zip(uniq.tolist(), (counts / counts.sum()).tolist()))


def tvd(draws, pmf: dict) -> float:
    """Total-variation distance of the draws' frequencies from ``pmf``;
    a drawn id outside it counts in full."""
    uniq, counts = np.unique(np.asarray(draws).reshape(-1),
                             return_counts=True)
    freq = dict(zip(uniq.tolist(), (counts / counts.sum()).tolist()))
    keys = set(freq) | set(pmf)
    return 0.5 * sum(abs(freq.get(k, 0.0) - pmf.get(k, 0.0)) for k in keys)


def pmf_tvd(a: dict, b: dict) -> float:
    return 0.5 * sum(abs(a.get(k, 0.0) - b.get(k, 0.0))
                     for k in set(a) | set(b))


def noise_tvd(pmf: dict, n: int) -> tuple:
    """(mean, standard deviation) of the distance ``n`` exact draws read:
    each id's count taken normal to its binomial, the ids independent
    (the multinomial's covariance only narrows it)."""
    p = np.asarray(list(pmf.values()))
    var = p * (1 - p) / n
    mean = 0.5 * np.sum(np.sqrt(2 * var / math.pi))
    sd = 0.5 * np.sqrt(np.sum(var) * (1 - 2 / math.pi))
    return float(mean), float(sd)


def tvd_bound(pmf: dict, n: int) -> float:
    mean, sd = noise_tvd(pmf, n)
    return mean + BOUND_SDS * sd


def draw_rows(adj: dict, rows, n: int, seed: int = 44) -> list:
    """``n`` draws of each row through the step's own draw entry."""
    import jax

    from euler_tpu.graph import device as device_graph

    per = 1000
    count = max(n // per, 1)
    fn = jax.jit(lambda a, nodes, key: device_graph.sample_neighbor(
        a, nodes, key, count))
    out = []
    for i, row in enumerate(rows):
        nodes = np.full(per, int(row), np.int32)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        out.append(np.asarray(jax.device_get(fn(adj, nodes, key))))
    return out


def alias_gathers(hlo_text: str, sizes) -> list:
    """Each fusion of an HLO module that reads an array of one of
    ``sizes`` elements (the alias tables' edge count, their row count):
    its name and kind, the array's shape and layout as the fused
    computation takes it, and what it gives. The module's own fusion
    lines name operands without shapes, so they are read from the fused
    computation's parameters."""
    pat = re.compile(r"\[(%s)\]\{" % "|".join(str(s) for s in sizes))
    header = re.compile(r"^%(\S+) \(.*\) -> (\S+) \{")
    param = re.compile(r"= (\S+\[\d+\]\{[^}]*\}) parameter")
    reads, comp = {}, None
    for line in hlo_text.splitlines():
        h = header.match(line)
        if h:
            comp = h.groups()
        elif comp and " parameter(" in line and pat.search(line):
            reads.setdefault(comp, []).append(param.search(line).group(1))
    out = []
    for (name, gives), arrays in reads.items():
        call = re.search(r"%%(\S+) = .* calls=%%%s[,\s].*?kind=(\w+)|"
                         r"%%(\S+) = .*kind=(\w+), calls=%%%s[,\s]"
                         % (re.escape(name), re.escape(name)), hlo_text)
        who = "" if call is None else "%s %s: " % (
            call.group(1) or call.group(3), call.group(2) or call.group(4))
        out.append(f"{who}{', '.join(arrays)} -> {gives}")
    return out


def study(manifest: str, n: int, hlo: str | None, cell: str = CELL,
          data_root: str | None = None) -> dict:
    sys.path.insert(0, ROOT)
    from benchmark import harness

    t0 = time.time()
    prep = harness.Prepared(manifest, cell, t0, require_chip=False,
                            data_root=data_root)
    try:
        spec = prep.spec
        out = {"platform": prep.devices[0].platform,
               "setup_s": round(time.time() - t0, 1),
               "degree_law": spec.degree_law()}
        adj = prep.consts["adj"]["et0"]
        assert "off" in adj and "cum" not in adj, sorted(adj)
        deg = np.diff(spec._out_cum, prepend=0)
        hubs = np.argsort(deg, kind="stable")[-20:][::-1]
        short = np.nonzero(deg < 10)[0][:20]
        rows = []
        t = time.time()
        drawn = draw_rows(adj, np.concatenate([hubs, short]), n)
        out["draw_s"] = round(time.time() - t, 1)
        for row, draws in zip(np.concatenate([hubs, short]), drawn):
            pmf = exact_pmf(spec, int(row))
            noise, _ = noise_tvd(pmf, draws.size)
            rows.append({
                "row": int(row), "degree": int(deg[row]),
                "distinct": len(pmf), "draws": int(draws.size),
                "tvd": tvd(draws, pmf), "noise_tvd": noise,
                "bound": tvd_bound(pmf, draws.size),
                "uniform_slots_tvd": pmf_tvd(slot_pmf(spec, int(row)), pmf),
            })
        out["rows"] = rows
        for name, sel in (("hubs", rows[:20]), ("short", rows[20:])):
            out[name] = {
                "tvd_max": max(r["tvd"] for r in sel),
                "tvd_over_noise_max": max(r["tvd"] / r["noise_tvd"]
                                          for r in sel),
                "all_under_bound": all(r["tvd"] <= r["bound"] for r in sel),
            }
        if hlo and os.path.isfile(hlo):
            with open(hlo) as f:
                out["alias_gathers"] = alias_gathers(
                    f.read(), (spec.num_edges, spec.num_nodes + 1))
        return out
    finally:
        prep.close()


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    p.add_argument("--workload", default=CELL)
    p.add_argument("--draws", type=int, default=1_000_000)
    p.add_argument("--hlo", default="")
    p.add_argument("--out", default="")
    a = p.parse_args(argv)
    code = 1
    try:
        result = study(a.manifest, a.draws, a.hlo or None, a.workload)
        if a.out:
            os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
            with open(a.out, "w") as f:
                json.dump(result, f)
        print(json.dumps({k: v for k, v in result.items() if k != "rows"}))
        code = 0
    except BaseException:  # noqa: BLE001 - said, then out by os._exit
        traceback.print_exc()
    finally:
        sys.stdout.flush()
        sys.stderr.flush()
        # as benchmark/run.py leaves: the TPU client, the engine's teams
        # and the memory sampler can hold an ordinary shutdown
        os._exit(code)


if __name__ == "__main__":
    main()
