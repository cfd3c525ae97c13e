"""Real-degree Reddit evidence: build a power-law graph at the REAL
edge budget (~114.6M directed edges over 232,965 nodes, mean ~490,
heavy tail), drive it end-to-end through convert + host engine load,
and measure what the reference-semantics questions actually need
measured (VERDICT r3 next-#2):

  --full              the 114M-edge build + load: generation time, .dat
                      bytes, achieved edge count, degree stats, engine
                      load time + RSS, the device-memory table (padded
                      slab at max_degree in {64, 256, 512} and at the
                      observed max — the unbuildable case — vs the
                      O(E) alias form), and device-sampling step timing
                      at the reference reddit recipe (batch 1000,
                      fanouts [4,4]) for the truncated-slab and exact
                      alias samplers.
  --truncation-study  the learning-cost question at a tractable scale:
                      a planted-community POWER-LAW graph (hub degrees
                      ~100x the slab caps) trained with device sampling
                      at max_degree in {8, 32, 128}, with the exact
                      alias sampler, and with the untruncated host
                      path; reports val micro-F1 and final loss per
                      variant. The alias row must match the host path
                      (both exact); the small-cap rows price the
                      truncation deviation from reference semantics
                      (CompactNode samples over ALL neighbors,
                      euler/core/compact_node.cc:42-101).

Both print one JSON summary; PERF.md records the numbers. The full
build is slow by nature (~114M edges through the line-block writer on
one core) and caches in --workdir: rerunning skips generation.

    JAX_PLATFORMS=cpu python scripts/reddit_heavytail.py --truncation-study
    python scripts/reddit_heavytail.py --full --workdir /root/repo/.data/reddit_ht
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def full_scale(workdir: str, num_edges: int, batch: int, steps: int) -> dict:
    import euler_tpu
    from euler_tpu.datasets import REDDIT_HEAVYTAIL, build_powerlaw
    from euler_tpu.graph import device as dg

    cfg = dict(REDDIT_HEAVYTAIL)
    cfg["num_edges"] = num_edges
    out: dict = {"config": cfg}

    t0 = time.time()
    build_powerlaw(workdir, progress_every=20000, **cfg)
    out["generate_s"] = round(time.time() - t0, 1)
    out["dat_bytes"] = sum(
        os.path.getsize(os.path.join(workdir, f))
        for f in os.listdir(workdir) if f.endswith(".dat")
    )

    rss0 = rss_mb()
    t1 = time.time()
    g = euler_tpu.Graph(directory=workdir)
    out["engine_load_s"] = round(time.time() - t1, 1)
    out["engine_rss_mb"] = round(rss_mb() - rss0, 1)

    n = cfg["num_nodes"]
    counts = np.zeros(n, np.int64)
    for lo in range(0, n, 65536):
        ids = np.arange(lo, min(lo + 65536, n))
        _, _, _, c = g.get_full_neighbor(ids, [0])
        counts[lo:lo + len(ids)] = c
    # Graph.num_edges counts edge-feature OBJECTS (this generator writes
    # none); the achieved adjacency size is the degree sum
    out["num_edges_achieved"] = int(counts.sum())
    out["degree"] = {
        "mean": round(float(counts.mean()), 1),
        "p99": int(np.percentile(counts, 99)),
        "max": int(counts.max()),
    }

    # device-memory table: slab (nbr+cum+packed where eligible) vs alias
    w_max = int(counts.max())
    mem = {}
    for w in (64, 256, 512, w_max):
        slab = (n + 2) * w * 8                      # nbr int32 + cum f32
        packed = (
            2 * ((w + 127) // 128) * (n + 2) * 128 * 4 if w <= 512 else None
        )
        mass_kept = float(np.minimum(counts, w).sum() / counts.sum())
        mem[f"slab_w{w}"] = {
            "slab_bytes": slab,
            "packed_bytes": packed,
            "edge_mass_kept": round(mass_kept, 4),
        }
    e = int(counts.sum())
    mem["alias_exact"] = {
        "bytes": 12 * e + 8 * (n + 2), "edge_mass_kept": 1.0,
    }
    out["device_memory"] = mem

    # device-sampling step timing at the reference reddit recipe
    # (batch 1000 roots x fanouts [4,4]); on CPU this is context, on a
    # TPU backend it is the real number
    import jax
    import jax.numpy as jnp

    t2 = time.time()
    aadj = dg.build_alias_adjacency(g, [0], n - 1)
    out["alias_build_s"] = round(time.time() - t2, 1)
    aadj = jax.device_put({k: jnp.asarray(v) for k, v in aadj.items()})

    def step(adj, key):
        roots = jax.random.randint(key, (batch,), 0, n)
        hops = dg.sample_fanout([adj, adj], roots, key, [4, 4])
        return hops[-1].sum()

    # adjacency is a jit ARGUMENT, not a closure capture: captured
    # device arrays are baked into the executable as constants, which
    # would keep the ~1.4 GB alias tables resident (immune to the del
    # below) through the slab phase's own device allocation
    f = jax.jit(step)
    f(aadj, jax.random.PRNGKey(0)).block_until_ready()
    t3 = time.time()
    for i in range(steps):
        r = f(aadj, jax.random.PRNGKey(i + 1))
    r.block_until_ready()
    dt = (time.time() - t3) / steps
    edges_per_step = batch * (4 + 4 * 4)
    out["alias_sampling"] = {
        "ms_per_step": round(dt * 1e3, 3),
        "edges_per_s": round(edges_per_step / dt),
        "platform": jax.default_backend(),
    }
    del aadj

    t4 = time.time()
    slab = dg.build_adjacency(g, [0], n - 1, max_degree=512)
    out["slab512_build_s"] = round(time.time() - t4, 1)
    slab = jax.device_put({k: jnp.asarray(v) for k, v in slab.items()})
    f(slab, jax.random.PRNGKey(0)).block_until_ready()
    t5 = time.time()
    for i in range(steps):
        r = f(slab, jax.random.PRNGKey(i + 1))
    r.block_until_ready()
    dt2 = (time.time() - t5) / steps
    out["slab512_sampling"] = {
        "ms_per_step": round(dt2 * 1e3, 3),
        "edges_per_s": round(edges_per_step / dt2),
    }
    out["peak_rss_mb"] = round(rss_mb(), 1)
    return out


def walk_study(
    pairs_per_cap: int = 400,
    seed: int = 11,
    caps=(64, 256, 512),
    num_nodes: int = 6000,
    num_edges: int = 600_000,
) -> dict:
    """Quantify the biased-walk truncation distortion the device.py
    docstring documents (device.py biased_random_walk: with max_degree
    truncation a dropped real neighbor of the PARENT classifies as
    d_tx=2 (1/q) instead of d_tx=1, on top of the truncated sampling
    support of the CURRENT node).

    Both one-step transition distributions are computed ANALYTICALLY
    (no sampling noise): the exact node2vec distribution from the host
    engine's full neighbor lists (reference BuildWeights semantics,
    euler/client/graph.cc:120-151) vs the truncated-slab model that
    mirrors build_adjacency(sorted=True, max_degree=W) +
    biased_random_walk exactly. Steps measured are the AFFECTED class:
    parent x is a truncated (hub) row, current v drawn from x's kept
    set — any walk step with a hub parent is in this class; the
    edge-mass share of such steps is reported alongside. Metrics per
    cap W: mean/max total-variation distance and the mean exact-mass
    misclassified 1 -> 1/q."""
    import euler_tpu
    from euler_tpu.datasets import build_powerlaw
    from euler_tpu.graph import device as dg

    n, e = num_nodes, num_edges
    d = tempfile.mkdtemp(prefix="walk_study_")
    try:
        build_powerlaw(d, num_nodes=n, num_edges=e, feature_dim=4,
                       label_dim=3, alpha=1.6, seed=seed)
        g = euler_tpu.Graph(directory=d)
    finally:
        # the native load copies the .dat bytes into the store (no
        # mmap), so the multi-MB workdir can go the moment the graph is
        # up — repeated invocations (incl. tests) must not litter /tmp
        shutil.rmtree(d, ignore_errors=True)
    full_nbr, full_w, _, cnt = g.get_full_neighbor(np.arange(n), [0])
    rows = []          # per-node (ids, weights) from the host engine
    off = 0
    for c in cnt:
        rows.append((full_nbr[off:off + c], full_w[off:off + c]))
        off += c
    rng = np.random.default_rng(seed)
    out = {
        "graph": {"num_nodes": n, "num_edges": int(cnt.sum()),
                  "mean_degree": round(float(cnt.mean()), 1),
                  "max_degree": int(cnt.max())},
        "caps": {},
    }

    def exact_dist(x_set, x_id, v, p, q):
        # adjacency beats the parent match (a parent self-loop is
        # d_tx=1): the reference merge's equality branch runs before
        # its candidate<parent check (euler/client/graph.cc:126-140)
        ids, w = rows[v]
        scale = np.where(
            np.isin(ids, x_set), 1.0,
            np.where(ids == x_id, 1.0 / p, 1.0 / q),
        )
        pr = w * scale
        return ids, pr / pr.sum()

    for W in caps:
        hubs = np.flatnonzero(cnt > W)
        if len(hubs) == 0:
            out["caps"][f"W{W}"] = {
                "rows_truncated": 0,
                "note": "cap >= observed max degree: no truncation",
            }
            continue
        adj = dg.build_adjacency(g, [0], n - 1, max_degree=W, sorted=True)
        nbr, deg = np.asarray(adj["nbr"]), np.asarray(adj["deg"])
        cum = np.asarray(adj["cum"], dtype=np.float64)
        # share of steps in the affected class: a step's support/classes
        # are wrong iff its PARENT row is truncated; under a uniform
        # edge-mass proxy that share is the edge mass leaving hub rows
        mass_from_hubs = float(cnt[hubs].sum() / cnt.sum())
        tvds, miscls = [], []
        for _ in range(pairs_per_cap):
            x = int(rng.choice(hubs))
            kept_x = nbr[x][:deg[x]]
            v = int(rng.choice(kept_x))
            if cnt[v] == 0 or deg[v] == 0:
                continue
            x_full = rows[x][0]
            for p, q in ((0.25, 4.0), (4.0, 0.25)):
                ids_e, pr_e = exact_dist(x_full, x, v, p, q)
                ids_set = {int(i) for i in ids_e}
                # truncated model: v's kept slots + weights from cum
                # diffs; membership against x's KEPT sorted row
                kv = nbr[v][:deg[v]]
                wv = np.diff(np.concatenate([[0.0], cum[v][:deg[v]]]))
                pos = np.searchsorted(kept_x, kv)
                in_x = (pos < deg[x]) & (
                    kept_x[np.clip(pos, 0, deg[x] - 1)] == kv
                )
                sc = np.where(
                    in_x, 1.0, np.where(kv == x, 1.0 / p, 1.0 / q)
                )
                pr_t = wv * sc
                pr_t = pr_t / pr_t.sum()
                t = {int(y): 0.0 for y in ids_set}
                for i, y in enumerate(kv):
                    t[int(y)] = t.get(int(y), 0.0) + pr_t[i]
                tvd = 0.5 * (
                    sum(abs(t.get(int(y), 0.0) - pe)
                        for y, pe in zip(ids_e, pr_e))
                    + sum(v2 for y, v2 in t.items()
                          if y not in ids_set)
                )
                tvds.append(tvd)
                # exact mass whose CLASS flips 1 -> 1/q: candidates the
                # device still reaches (in v's kept row) that are real
                # neighbors of x but absent from x's kept row. Mass on
                # candidates dropped from v's row is SUPPORT truncation,
                # counted by the TVD, not here.
                flipped = (
                    np.isin(ids_e, x_full)
                    & ~np.isin(ids_e, kept_x)
                    & np.isin(ids_e, kv)
                )
                miscls.append(float(pr_e[flipped].sum()))
        entry = {
            "rows_truncated": int(len(hubs)),
            "edge_mass_from_truncated_rows": round(mass_from_hubs, 4),
        }
        if tvds:  # all-dead-end draws leave no valid pairs; avoid NaN
            entry.update(
                mean_tvd=round(float(np.mean(tvds)), 4),
                max_tvd=round(float(np.max(tvds)), 4),
                mean_exact_mass_misclassified=round(
                    float(np.mean(miscls)), 4
                ),
            )
        else:
            entry["note"] = "no valid (hub parent, sampleable v) pairs"
        out["caps"][f"W{W}"] = entry

    # The exact device alternative: alias + rejection
    # (device.alias_biased_random_walk). Empirical — the sampler is
    # stochastic, so its TVD floor is sampling noise ~0.4*sqrt(S/K) for
    # support size S — on the SAME affected step class (hub parent).
    out["alias_rejection"] = _alias_rejection_study(
        g, rows, cnt, seed=seed, pairs=min(pairs_per_cap, 40),
    )
    return out


def _alias_rejection_study(g, rows, cnt, seed: int, pairs: int,
                           draws: int = 20000) -> dict:
    """Empirical TVD of the exact alias+rejection biased step vs the
    analytic node2vec distribution, over hub-parent steps (the class the
    truncated slab distorts at mean TVD ~0.35). Expected: TVD at the
    sampling-noise floor for `draws` draws."""
    import jax
    from euler_tpu.graph import device as dg

    n = len(rows)
    adj = dg.build_alias_adjacency(g, [0], n - 1, sorted=True)
    rng = np.random.default_rng(seed + 1)
    hubs = np.flatnonzero(cnt >= np.quantile(cnt[cnt > 0], 0.99))
    if len(hubs) == 0:
        return {"note": "no hub rows"}
    tvds = []
    T = dg.DEFAULT_WALK_TRIALS
    for p, q in ((0.25, 4.0), (4.0, 0.25)):
        step = jax.jit(
            lambda cur, par, key, p=p, q=q: dg._alias_biased_step(
                adj, cur, par, key, p, q, T
            )
        )
        for i in range(pairs):
            x = int(rng.choice(hubs))
            x_full, _ = rows[x]
            if len(x_full) == 0:
                continue
            v = int(rng.choice(x_full))
            ids, w = rows[v]
            if len(ids) == 0 or w.sum() <= 0:
                continue
            # analytic target with the reference's branch order
            scale = np.where(
                np.isin(ids, x_full), 1.0,
                np.where(ids == x, 1.0 / p, 1.0 / q),
            )
            pr = w * scale
            pr = pr / pr.sum()
            cur = np.full(draws, v, np.int32)
            par = np.full(draws, x, np.int32)
            got = np.asarray(
                step(cur, par, jax.random.PRNGKey(seed * 1000 + i))
            )
            uy, uc = np.unique(got, return_counts=True)
            emp = {int(a): b / draws for a, b in zip(uy, uc)}
            support = {int(y) for y in ids}
            tvd = 0.5 * (
                sum(abs(emp.get(int(y), 0.0) - pe)
                    for y, pe in zip(ids, pr))
                + sum(pv for y, pv in emp.items() if y not in support)
            )
            tvds.append(tvd)
    if not tvds:
        return {"note": "no valid pairs"}
    return {
        "mean_tvd": round(float(np.mean(tvds)), 4),
        "max_tvd": round(float(np.max(tvds)), 4),
        "pairs": len(tvds),
        "draws_per_pair": draws,
        "trials": T,
    }


def truncation_study(steps: int, batch: int) -> dict:
    """Train the same GraphSAGE on a heavy-tailed planted graph under
    each sampler form; report val micro-F1 + final loss."""
    import euler_tpu
    from euler_tpu import train as train_lib
    from euler_tpu.datasets import (
        build_planted, nearest_centroid_accuracy,
    )
    from euler_tpu.graph import device as dg
    from euler_tpu.models import SupervisedGraphSage

    n, k_comm, fdim = 6000, 4, 16
    d = tempfile.mkdtemp(prefix="trunc_study_")
    try:
        out_dir, info = build_planted(
            d, num_nodes=n, num_communities=k_comm, feature_dim=fdim,
            avg_degree=60, max_degree=1500, alpha=1.6, noise=1.2,
            num_partitions=2, seed=29,
        )
        g = euler_tpu.Graph(directory=out_dir)
    finally:
        shutil.rmtree(d, ignore_errors=True)  # store holds a copy
    counts = g.get_full_neighbor(np.arange(n), [0])[3]
    summary: dict = {
        "graph": {
            "num_nodes": n,
            "mean_degree": round(float(counts.mean()), 1),
            "max_degree": int(counts.max()),
        },
        "feat_acc": round(nearest_centroid_accuracy(info, False), 3),
        "hop1_acc": round(nearest_centroid_accuracy(info, True), 3),
        "variants": {},
    }

    def run(name, device_sampling, max_degree=None, alias=False):
        model = SupervisedGraphSage(
            label_idx=0, label_dim=k_comm, metapath=[[0], [0]],
            fanouts=[10, 10], dim=32, feature_idx=1, feature_dim=fdim,
            max_id=n - 1, sigmoid_loss=False,
            device_sampling=device_sampling, device_features=True,
        )
        if device_sampling:
            model.set_sampling_options(max_degree=max_degree, alias=alias)
        state, history = train_lib.train(
            model, g, lambda s: g.sample_node(batch, -1),
            num_steps=steps, learning_rate=0.01, optimizer="adam",
            log_every=50, seed=5,
        )
        ids = np.arange(n, dtype=np.int64)
        batches = [ids[i:i + 400] for i in range(0, n, 400)]
        f1 = train_lib.evaluate(model, g, batches, state)["f1"]
        summary["variants"][name] = {
            "f1": round(float(f1), 4),
            "final_loss": round(
                float(np.mean([h["loss"] for h in history[-3:]])), 4
            ),
        }

    run("host_exact", device_sampling=False)
    for cap in (8, 32, 128):
        run(f"slab_w{cap}", device_sampling=True, max_degree=cap)
    run("alias_exact", device_sampling=True, alias=True)
    return summary


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--truncation-study", action="store_true")
    ap.add_argument("--walk-study", action="store_true")
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--num-edges", type=int, default=114_600_000,
                    help="edge target; the generator (unique-fill + "
                    "Gumbel-top-k hub rows) lands <1%% under this "
                    "(measured 0.8%% under at the Reddit recipe)")
    ap.add_argument("--batch", type=int, default=1000)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--study-steps", type=int, default=400)
    ap.add_argument("--study-batch", type=int, default=256)
    args = ap.parse_args()
    out = {}
    if args.truncation_study:
        out["truncation_study"] = truncation_study(
            args.study_steps, args.study_batch
        )
    if args.walk_study:
        out["walk_study"] = walk_study()
    if args.full:
        # the cache every --full run shares (datasets.heavytail_cache_dir):
        # the ~2 GB graph is built once
        from euler_tpu.datasets import heavytail_cache_dir

        wd = args.workdir or heavytail_cache_dir()
        out["full_scale"] = full_scale(
            wd, args.num_edges, args.batch, args.steps
        )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
