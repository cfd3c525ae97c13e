"""The comparison that decides ``correct`` for a training cell.

What the timed path produced in its first three steps (the same
``train()`` call whose later steps are the window) against the plain
reference, on the rows those steps really used:

This module knows no model family: the configuration's reference module
(``sage_reference.py`` states the protocol) says which fan-outs a step
draws, what the reference trains on and how a batch is cut to its first
rows, and which named leaves of the program's state are compared. Here
are the numbers, the two-reference bracket and the verdict:

* ``draw_foreign``  drawn ids that are not neighbours of their parent in
  the benchmark's graph function (exact, limit 0);
* ``draw_skew``     |mean quantile of the picked slot - 0.5| over all
  draws (a sampler stuck on one slot reads 0.5);
* ``loss_gap``      worst of the three steps' |loss - reference| over the
  reference's loss;
* ``grad_gap``      worst leaf of the first gradient as the optimizer got
  it (for Adam: its first moment after one step, over 1 - b1): gap
  between the program's norm and the reference's, against the reference's
  norm of that leaf or of the median leaf, whichever is larger;
* ``change_gap``    the same measure on the change of the compared
  leaves (the parameters; a family's stores too) after the three steps,
  leaving out leaves whose reference gradient is nought to rounding
  (under a thousandth of the median leaf's).

Two references bracket what the configuration states. The recipe is
float32 and the program leaves its matmuls at the platform's default
precision, which on the TPU rounds the operands of every matmul to
bfloat16 and accumulates in float32. Against the float32 ``highest``
reference alone the program reads 3e-4..1e-3 on the chip, no further than
the bfloat16 control does (8e-4..4e-3): no limit separates them (PR 26
chip runs, PERF.md section 2). So each gap is taken to the nearer of the
two: the reference at ``highest`` (an implementation that computes more
exactly comes closer to it) and the same reference at the default
precision (what the configuration states; on a CPU the two coincide).

The readings each limit was set from are in PERF.md section 2.
"""

from __future__ import annotations

import numpy as np


def leaf_norms(tree: dict) -> dict:
    return {
        k: float(np.linalg.norm(np.asarray(v, dtype=np.float64)))
        for k, v in tree.items()
    }


def worst_leaf_gap(prog: dict, ref: dict, drop=()) -> float:
    """max over the reference's leaves, but those in ``drop``, of
    |‖prog‖ - ‖ref‖| / max(‖ref‖, median ‖ref‖)."""
    pn, rn = leaf_norms(prog), leaf_norms(ref)
    names = [k for k in rn if k not in drop]
    med = float(np.median([rn[k] for k in names]))
    return max(
        abs(pn[k] - rn[k]) / max(rn[k], med, 1e-30) for k in names
    )


def still_leaves(ref_grad: dict) -> set:
    """Leaves whose reference gradient is nought to rounding: they move
    under Adam by round-off alone and are left out of the change."""
    rn = leaf_norms(ref_grad)
    med = float(np.median(list(rn.values())))
    return {k for k, v in rn.items() if v < 1e-3 * med}


def draw_numbers(spec, hops: list, fanouts: list) -> tuple:
    """(foreign count, skew) of the draws of one step. hops: per-hop flat
    id arrays, hop h+1 holding fanouts[h] picks per row of hop h."""
    foreign = 0
    quantiles = []
    for h, fan in enumerate(fanouts):
        parents = np.asarray(hops[h], dtype=np.int64).reshape(-1)
        picks = np.asarray(hops[h + 1], dtype=np.int64).reshape(
            len(parents), fan)
        ok_parent = (parents >= 0) & (parents < spec.num_nodes)
        safe = np.where(ok_parent, parents, 0)
        deg = spec.degrees(safe)
        slab = spec.neighbor_slab(safe)
        live = np.arange(slab.shape[1])[None, :] < deg[:, None]
        # [n, fan, W]: which live slots hold the picked id
        hit = (slab[:, None, :] == picks[:, :, None]) & live[:, None, :]
        found = hit.any(axis=2) & ok_parent[:, None]
        foreign += int((~found).sum())
        slot = hit.argmax(axis=2)
        q = (slot + 0.5) / deg[:, None]
        quantiles.append(q[found])
    q = np.concatenate(quantiles) if quantiles else np.zeros(0)
    skew = abs(float(q.mean()) - 0.5) if len(q) else 0.5
    return foreign, skew


def compare(cfg: dict, spec, ref, captured: dict, dtype=None,
            batch_rows=None) -> dict:
    """The cell's numbers. ``captured`` holds what the hook took from the
    timed path: ``start`` (the reference-named leaves the steps began
    from), ``hops`` (per step, per hop), ``losses``, ``grad1`` and
    ``end`` (the compared leaves after the last captured step).

    ``dtype`` puts the reference, computed in that type, in the
    program's place (the control); ``batch_rows`` puts the reference on
    the first rows only in its place (the planted faults)."""
    import jax.numpy as jnp

    start = {k: jnp.asarray(v) for k, v in captured["start"].items()}

    def change_of(end: dict) -> dict:
        return {k: np.asarray(end[k]) - np.asarray(start[k]) for k in end}

    if "_references" not in captured:
        # kept beside what was captured: the control and the faults of a
        # calibration are held against the same two references
        batches = [
            ref.reference_batch(spec, hops) for hops in captured["hops"]]
        refs = []
        for precision in ("highest", None):
            r_losses, r_grad, r_end = ref.train_steps(
                cfg, start, batches, precision=precision)
            refs.append((
                r_losses,
                {k: np.asarray(v) for k, v in r_grad.items()},
                change_of(r_end),
            ))
        captured["_references"] = (batches, refs)
    batches, refs = captured["_references"]
    if dtype is not None or batch_rows is not None:
        sub = batches
        if batch_rows is not None:
            sub = [ref.batch_rows(cfg, b, batch_rows) for b in batches]
        losses, grad, end = ref.train_steps(
            cfg, start, sub, dtype or jnp.float32)
        grad = {k: np.asarray(v) for k, v in grad.items()}
    else:
        losses = captured["losses"]
        grad = captured["grad1"]
        end = captured["end"]
    change = change_of(end)
    fanouts = ref.drawn_fanouts(cfg)
    foreign, skews = 0, []
    for hops in captured["hops"]:
        f, s = draw_numbers(spec, hops, fanouts)
        foreign += f
        skews.append(s)
    return {
        "draw_foreign": float(foreign),
        "draw_skew": float(max(skews)),
        # each gap to the nearer of the two references
        "loss_gap": float(min(
            max(abs(a - b) / abs(b) for a, b in zip(losses, r[0]))
            for r in refs
        )),
        "grad_gap": min(worst_leaf_gap(grad, r[1]) for r in refs),
        "change_gap": min(
            worst_leaf_gap(change, r[2], drop=still_leaves(r[1]))
            for r in refs
        ),
    }


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}). Every limit has to be
    stated; a number with no limit is a fault of the configuration."""
    table = {}
    ok = True
    for name, value in numbers.items():
        limit = limits[name]
        good = bool(np.isfinite(value)) and value <= limit
        ok = ok and good
        table[name] = {"value": value, "limit": limit}
    return ok, table
