"""Plain reference and adapter of one full-neighbourhood GCN training
step: the supervised GCN of the upstream project (alibaba/euler
``tf_euler/python/run_loop.py --model gcn`` under its own flag defaults,
``models/gcn.py:26`` SupervisedGCN, ``encoders.py:165-215`` GCNEncoder,
``sparse_aggregators.py:37-81`` and ``euler_ops.get_multi_hop_neighbor``,
``neighbor_ops.py:110-116``). RAGGED, from the graph function itself, in
numpy and straightforward ``jax.numpy``, float32 at ``highest`` matmul
precision. Imports nothing of the program.

One step on ``B`` roots:

1. hop 1: every neighbour of every root (a root's ``degree`` true
   neighbours, whatever the widest row of the graph is), the ids made
   unique (``np.unique``): the set ``s1``. Hop 2: every neighbour of every
   node of ``s1``, made unique: ``s2``. No node is left out, none comes
   twice in a set;
2. the feature rows of the roots, of ``s1`` and of ``s2`` from the graph
   function, the roots' labels;
3. layer 1 (mean aggregator, relu on each branch, branches added) on the
   roots and on ``s1``: ``relu(x W_self0) + relu(mean W_neigh0)``, the
   mean over the node's true neighbours in the next hop's set. A row of
   the graph function that lists one neighbour twice has two edges to
   that node: its message is in the mean twice and the degree counts
   both;
4. layer 2 (no activation) on the roots: ``h0 W_self1 + mean(h1) W_neigh1``
   over each root's true neighbours in ``s1``;
5. a linear classifier, sigmoid cross-entropy averaged over every
   element, the gradient by ``jax.grad`` of this forward, Adam written
   out.

What it does not share with the program: no padding to a cap (its one
fill, ``bucketed``, adds zero rows for the compiler's sake and is held
exact by the tests), no sort, no mask, no ``segment_sum``. A mean over a ragged edge list is a
difference of a float64 running sum at the list's ends (features, which
carry no gradient), or a product with the ``[B, |s1|]`` matrix that holds
``1/degree`` an edge (layer 2's, at ``highest`` whatever the precision of
the weights' matmuls: the program's mean is a float32 sum).

The adapter's side (the protocol is stated in
``benchmark/sage_reference.py``). The program's step takes ``params``
and ``opt_state``; a device-sampled batch is the roots and a seed the
family does not read. ``drawn_hops`` runs the module's own expansion
(``_hops_adjs``: ``graph/device.py`` ``multi_hop_neighbor`` as inside the
step), jitted alone on the step's batch, and hands on the roots, both
hops' padded sets and each hop's unmasked edges as ``(position in the
hop before, nodes[dst])`` pairs, whatever layout the padded COO has.
``reference_batch``, which is handed the graph function, JUDGES THAT
EXPANSION EXACTLY, edge by edge: the unmasked edges of a hop as a
multiset against the true edges out of the hop before (``missing``:
true edges absent from it; ``extra``: unmasked slots that are no edge),
the hop's set against the true set (``foreign``: ids that are no
neighbour of the hop before; ``dropped``: neighbours that are not in it,
as a cap that does not hold drops them; ``twice``: a node more than
once). The family draws nothing, so ``drawn_fanouts`` is empty and
``check.py`` has no draw to judge (``draw_foreign`` 0, ``draw_skew`` 0.5
by its rule for no draws); the five counts enter the verdict through the
leaf ``expansion_off`` of ``change_gap``: the reference's leaf carries
their sum (it is where the graph function is at hand) against a
program's leaf of nought, so one such edge or node reads a gap of one
over the median leaf's norm, or 1. A second leaf, ``overflow_nodes``,
holds the program's own count of nodes past a cap over the captured
steps (``multi_hop_neighbor``'s ``overflow``; a program from before the
count reads nought) against a reference of nought. The reference itself
is handed the step's ROOTS alone and expands them anew.

Compared after step 1: the first gradient of the six parameters as Adam
got it (``mu`` over ``1 - b1``). After step 3: their change, and the two
leaves above.

``FAULTS`` are the family's planted faults: this reference with one rule
broken, put in the program's place as the control is
(``scalable_sage_reddit_faults.py --workload gcn_ppi_device_train`` does,
on the chip). A sound implementation never takes those branches.
"""

from __future__ import annotations

import functools
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

log = logging.getLogger("benchmark")

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
EXPANSION, OVERFLOW = "expansion_off", "overflow_nodes"
# what the faults' runner counts in its ``twice`` column: the leaf's length
TWICE = EXPANSION
# one rule of the family broken, by name (``expand``, ``loss_fn``)
FAULTS = ("neighbour_dropped", "padding_counted", "shared_neighbour_once",
          "self_left_out", "second_hop_not_aggregated")
COUNTS = ("foreign", "missing", "extra", "twice", "dropped")

# What the adapter remembers of the train() call in progress: the
# protocol hands ``compared_state`` the program's state alone, and the
# leaf ``overflow_nodes`` is the program's own count over the captured
# steps' expansions. Set by ``init_state``, filled by ``drawn_hops``.
_run = {"overflow": []}


# ---- the graph function's ragged neighbourhoods ----

def neighbours(spec, ids) -> tuple:
    """(position in ``ids``, neighbour id) of every true edge out of
    ``ids``, in the order of ``ids`` and of each row's slots."""
    ids = np.asarray(ids, np.int64).reshape(-1)
    slab = spec.neighbor_slab(ids)
    live = np.arange(slab.shape[1])[None, :] < spec.degrees(ids)[:, None]
    pos = np.broadcast_to(np.arange(len(ids))[:, None], slab.shape)
    return pos[live], slab[live]


def _first_parent_only(pos, child) -> tuple:
    """The edges that are the first, in parent order, to name their
    child (the fault ``shared_neighbour_once``)."""
    _, first = np.unique(child, return_index=True)
    keep = np.sort(first)
    return pos[keep], child[keep]


def expand(spec, roots, fault=None) -> dict:
    """The two-hop full neighbourhood of ``roots``, ragged: the sets
    ``s1`` and ``s2`` (ids, each once, in id order) and the true edges of
    each hop as (parent position, index into the next set)."""
    roots = np.asarray(roots, np.int64).reshape(-1)
    p0, c0 = neighbours(spec, roots)
    s1 = np.unique(c0)
    p1, c1 = neighbours(spec, s1)
    s2 = np.unique(c1)
    dropped = 0
    if fault == "neighbour_dropped":
        # what a cap a tenth too small does: the largest ids of hop 2
        # are left out, with their edges
        dropped = len(s2) // 10
        s2 = s2[:len(s2) - dropped]
        keep = np.isin(c1, s2)
        p1, c1 = p1[keep], c1[keep]
    if fault == "shared_neighbour_once":
        p0, c0 = _first_parent_only(p0, c0)
        p1, c1 = _first_parent_only(p1, c1)
    return {
        "roots": roots, "s1": s1, "s2": s2, "dropped": dropped,
        "e0": (p0, np.searchsorted(s1, c0)),
        "e1": (p1, np.searchsorted(s2, c1)),
    }


def _denominator(parents: int, pos, slots=None) -> tuple:
    """(edges of each parent, what its mean divides by): the count, or
    ``slots`` for every parent (the fault ``padding_counted``)."""
    count = np.bincount(pos, minlength=parents)
    return count, np.full(parents, slots) if slots else np.maximum(count, 1)


def ragged_mean(parents: int, pos, rows, slots=None) -> np.ndarray:
    """[parents, F] float32: the mean of ``rows`` [E, F] over the edges of
    each parent (``pos`` [E], ascending), nought for a parent with none.
    A float64 running sum read at each parent's ends."""
    count, denom = _denominator(parents, pos, slots)
    ends = np.cumsum(count)
    run = np.concatenate([
        np.zeros((1, rows.shape[1])),
        np.cumsum(rows, axis=0, dtype=np.float64)])
    total = run[ends] - run[ends - count]
    return (total / denom[:, None]).astype(np.float32)


def mean_matrix(parents: int, children: int, edges, slots=None):
    """[parents, children] float32 holding 1/degree an edge (an edge
    listed twice holds it twice)."""
    pos, child = edges
    _, denom = _denominator(parents, pos, slots)
    out = np.zeros((parents, children), np.float32)
    np.add.at(out, (pos, child), (1.0 / denom[pos]).astype(np.float32))
    return out


# the sound arrays of the last few steps: both references (``highest`` and
# the platform's default precision) and the control follow the same steps
_arrays: dict = {}


def step_arrays(spec, roots, fault=None) -> tuple:
    """(arrays, nodes a fault dropped). What one step's forward reads,
    from the graph function: ``x0`` [B, F] and ``x1`` [|s1|, F] feature
    rows, ``m0`` and ``m1`` the means of their neighbours' feature rows,
    ``a`` the [B, |s1|] mean matrix of layer 2, ``y`` the roots' labels."""
    key = (id(spec), np.asarray(roots).tobytes())
    if fault is None and key in _arrays:
        return _arrays[key], 0
    x = expand(spec, roots, fault)
    slots = spec.max_degree if fault == "padding_counted" else None
    b, n1 = len(x["roots"]), len(x["s1"])
    x1, x2 = spec.features(x["s1"]), spec.features(x["s2"])
    (p0, c0), (p1, c1) = x["e0"], x["e1"]
    arrays = {
        "x0": spec.features(x["roots"]), "x1": x1,
        "m0": ragged_mean(b, p0, x1[c0], slots),
        "m1": ragged_mean(n1, p1, x2[c1], slots),
        "a": mean_matrix(b, n1, x["e0"], slots),
        "y": spec.labels(x["roots"]),
    }
    if fault is None:
        while len(_arrays) >= 4:
            _arrays.pop(next(iter(_arrays)))
        _arrays[key] = arrays
    return arrays, x["dropped"]


# ---- the mathematics ----

def param_shapes(cfg: dict) -> dict:
    f, d, c = cfg["feature_dim"], cfg["dim"], cfg["num_classes"]
    if cfg["aggregator"] != "mean" or len(cfg["fanouts"]) != 2:
        raise ValueError("reference covers two mean-aggregator layers")
    return {
        "w_self0": (f, d), "w_neigh0": (f, d),
        "w_self1": (d, d), "w_neigh1": (d, d),
        "w_out": (d, c), "b_out": (c,),
    }


def init_params(cfg: dict, key) -> dict:
    """The benchmark's weights from the seed: N(0, 1/fan_in) kernels, zero
    bias, float32. One traced function, jit it at the call site."""
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        if len(shape) == 1:
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32
            ) * (1.0 / shape[0]) ** 0.5
    return out


def loss_fn(params, arrays, precision="highest", fault=None):
    """Mean loss of one step's ``step_arrays``."""
    def branch(x, w, act):
        y = jnp.dot(x, params[w], precision=precision)
        return jax.nn.relu(y) if act else y

    def layer(self_x, mean_x, tail, act):
        out = branch(mean_x, "w_neigh" + tail, act)
        if fault != "self_left_out":
            out = branch(self_x, "w_self" + tail, act) + out
        return out

    m1 = arrays["m1"]
    if fault == "second_hop_not_aggregated":
        m1 = jnp.zeros_like(m1)
    h0 = layer(arrays["x0"], arrays["m0"], "0", True)
    h1 = layer(arrays["x1"], m1, "0", True)
    # the mean is a float32 sum in the recipe, whatever the precision of
    # the weights' matmuls
    mean_h1 = jnp.dot(arrays["a"], h1, precision="highest")
    z = layer(h0, mean_h1, "1", False)
    logits = jnp.dot(z, params["w_out"], precision=precision) \
        + params["b_out"]
    y = arrays["y"]
    # max(x, 0) - x*y + log(1 + exp(-|x|)), mean over every element
    per = (jnp.maximum(logits, 0) - logits * y
           + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    return per.mean()


def adam_init(params: dict) -> dict:
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    return {"m": zeros, "v": dict(zeros), "t": 0}


@jax.jit
def adam_update(params, grads, opt, lr):
    """One Adam step, written out; one program for every step of every
    run (the step count is an argument, the shapes are the recipe's)."""
    t = opt["t"] + 1
    m = {k: ADAM_B1 * opt["m"][k] + (1 - ADAM_B1) * grads[k] for k in params}
    v = {k: ADAM_B2 * opt["v"][k] + (1 - ADAM_B2) * grads[k] ** 2
         for k in params}
    new = {}
    for k in params:
        mhat = m[k] / (1 - ADAM_B1 ** t)
        vhat = v[k] / (1 - ADAM_B2 ** t)
        new[k] = params[k] - lr * mhat / (jnp.sqrt(vhat) + ADAM_EPS)
    return new, {"m": m, "v": v, "t": t}


# rows the arrays of hop 1 are filled up to before the jitted call
BUCKET = 4096


def bucketed(arrays: dict) -> dict:
    """``arrays`` with hop 1 filled up with zero rows (``x1``, ``m1``) and
    the mean matrix with zero columns to the next multiple of ``BUCKET``,
    so that the steps of a run, whose hop-1 sets differ by a few hundred
    nodes, share one compiled program (a compile takes 8 s on the chip,
    and the check is part of a run's time). Exact: a zero row's hidden
    row is relu(0) + relu(0) = 0, its column of the mean matrix is 0, and
    neither reaches the loss or a gradient."""
    fill = -len(arrays["x1"]) % BUCKET
    out = dict(arrays)
    for k in ("x1", "m1"):
        out[k] = np.pad(arrays[k], ((0, fill), (0, 0)))
    out["a"] = np.pad(arrays["a"], ((0, 0), (0, fill)))
    return out


@functools.lru_cache(maxsize=32)
def _value_and_grad(dtype, precision, fault):
    def step(p, arrays):
        def f(p_low):
            low = {k: v.astype(dtype) for k, v in arrays.items()}
            return loss_fn(p_low, low, precision, fault)

        loss, g = jax.value_and_grad(f)(
            {k: v.astype(dtype) for k, v in p.items()})
        return loss.astype(jnp.float32), {
            k: v.astype(jnp.float32) for k, v in g.items()}

    return jax.jit(step)


def train_steps(cfg: dict, start: dict, batches: list, dtype=jnp.float32,
                precision="highest", fault=None):
    """Follow ``len(batches)`` steps from the parameters of ``start``.
    Returns (losses, first gradient, compared leaves after the last
    step), all float32. ``precision=None``: the same float32 step with
    the weights' matmuls at the platform's default precision. ``dtype``
    bfloat16 is the control: parameters, features, means, activations,
    loss and gradients in bfloat16; float32 master weights and Adam."""
    assert fault is None or fault in FAULTS, fault
    params = {k: jnp.asarray(start[k]) for k in param_shapes(cfg)}
    step = _value_and_grad(jnp.dtype(dtype), precision, fault)
    opt = adam_init(params)
    losses, first, off = [], None, 0
    clock = {"arrays": 0.0, "steps": 0.0}
    for batch in batches:
        t0 = time.time()
        arrays, dropped = step_arrays(batch["spec"], batch["roots"], fault)
        off += batch["off"] + dropped
        t1 = time.time()
        loss, g = step(params, bucketed(arrays))
        if first is None:
            first = g
        params, opt = adam_update(params, g, opt, cfg["learning_rate"])
        losses.append(float(loss))
        clock["arrays"] += t1 - t0
        clock["steps"] += time.time() - t1
    log.info(
        "gcn reference: %d steps followed (%s, precision %s%s): the ragged "
        "arrays from the graph function %.1f s, the steps %.1f s",
        len(batches), jnp.dtype(dtype).name, precision,
        ", fault " + fault if fault else "", clock["arrays"], clock["steps"])
    end = dict(params)
    # what the exact judgement of the program's expansions found off
    # (``reference_batch``); a fault that leaves nodes out adds its own
    end[EXPANSION] = np.array([off], np.float32)
    end[OVERFLOW] = np.zeros(1, np.float32)
    return losses, first, end


# ---- adapter: the reference's names <-> the program's parameter tree ----
_PROGRAM_PATHS = {
    "w_self0": ("encoder", "MeanAggregator_0", "Dense_0", "Dense_0", "kernel"),
    "w_neigh0": ("encoder", "MeanAggregator_0", "Dense_1", "Dense_0", "kernel"),
    "w_self1": ("encoder", "MeanAggregator_1", "Dense_0", "Dense_0", "kernel"),
    "w_neigh1": ("encoder", "MeanAggregator_1", "Dense_1", "Dense_0", "kernel"),
    "w_out": ("predict", "kernel"),
    "b_out": ("predict", "bias"),
}


def to_program(params: dict) -> dict:
    tree: dict = {}
    for name, path in _PROGRAM_PATHS.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = params[name]
    return tree


def from_program(tree) -> dict:
    out = {}
    for name, path in _PROGRAM_PATHS.items():
        node = tree
        for k in path:
            node = node[k]
        out[name] = node
    return out


def init_state(cfg: dict, key, optimizer) -> tuple:
    """(start, state): the benchmark's weights from ``key`` in one jitted
    call, under the reference's names, and what the program's step takes
    (``params`` and the optimizer's state over them; the harness adds
    ``consts``)."""
    start = jax.jit(lambda k: init_params(cfg, k))(key)
    tree = to_program(start)
    start = dict(start)
    start[EXPANSION] = start[OVERFLOW] = np.zeros(1, np.float32)
    _run.update(overflow=[])
    return start, {"params": tree, "opt_state": optimizer.init(tree)}


def first_gradient(state) -> dict:
    """The first gradient as the optimizer got it, from the program's
    Adam state after one step: mu_1 = (1 - b1) * g_1."""
    mu = jax.device_get(state["opt_state"])[0].mu
    return {
        k: np.asarray(v) / (1.0 - ADAM_B1)
        for k, v in from_program(mu).items()
    }


def compared_state(state) -> dict:
    """The leaves whose change after the captured steps is compared: the
    parameters, the program's own count of nodes past a cap over the
    captured steps, and (nought on the program's side: see
    ``reference_batch``) the exact judgement's leaf."""
    out = {
        k: np.asarray(v)
        for k, v in from_program(jax.device_get(state["params"])).items()
    }
    out[OVERFLOW] = np.array([sum(_run["overflow"])], np.float32)
    out[EXPANSION] = np.zeros(1, np.float32)
    return out


def drawn_fanouts(cfg: dict) -> list:
    """Nothing is drawn: every neighbour is taken."""
    return []


def judge_hop(spec, parents, nodes, e_pos, e_child) -> dict:
    """One hop of the program's expansion against the graph function,
    exactly. ``parents`` [C] the ids of the hop before (a default id, or
    any id out of ``0 .. N - 1``, is padding and has no edge), ``nodes``
    the hop's set padded with such ids, ``e_pos``/``e_child`` the
    unmasked edges as (position in ``parents``, child id)."""
    big = spec.num_nodes
    parents = np.asarray(parents, np.int64).reshape(-1)
    nodes = np.asarray(nodes, np.int64).reshape(-1)
    real = np.flatnonzero((parents >= 0) & (parents < big))
    t_pos, t_child = neighbours(spec, parents[real])
    t_pos = real[t_pos]
    # the two edge lists as multisets of (parent position, child id)
    keys = [np.asarray(pos, np.int64) * (big + 2)
            + np.clip(np.asarray(child, np.int64), -1, big) + 1
            for pos, child in ((e_pos, e_child), (t_pos, t_child))]
    uniq = [np.unique(k, return_counts=True) for k in keys]
    both, inv = np.unique(np.concatenate([u[0] for u in uniq]),
                          return_inverse=True)
    n = len(uniq[0][0])
    diff = (np.bincount(inv[:n], uniq[0][1], len(both))
            - np.bincount(inv[n:], uniq[1][1], len(both)))
    have = nodes[(nodes >= 0) & (nodes < big)]
    truth = np.unique(t_child)
    return {
        "foreign": len(np.setdiff1d(have, truth))
        + int(((nodes < 0) | (nodes > big)).sum()),
        "missing": int(-diff[diff < 0].sum()),
        "extra": int(diff[diff > 0].sum()),
        "twice": len(have) - len(np.unique(have)),
        "dropped": len(np.setdiff1d(truth, have)),
        "set": len(truth), "edges": len(t_child),
        "listed_twice": len(keys[1]) - len(uniq[1][0]),
    }


@functools.lru_cache(maxsize=8)
def _expansion_fn(module):
    def expansion(batch, consts):
        feats, adjs = module.apply(
            {"params": {}}, batch, consts, method=module._hops_adjs)
        sets = [f["gids"] for f in feats]
        coo = [(a["src"], a["dst"], a["mask"]) for a in adjs]
        # a program from before the count has none: it then reads nought
        over = sum(a["overflow"] for a in adjs if "overflow" in a)
        return sets, coo, jnp.asarray(over, jnp.int32)

    return jax.jit(expansion)


def drawn_hops(model, state, batch) -> list:
    """The program's own expansion of this step, jitted alone on the
    step's batch (``_hops_adjs``, as inside its step); a host-expanded
    batch carries sets and adjacencies. Returns the roots and both hops'
    padded sets, then each hop's unmasked edges as (position in the hop
    before, child id) pairs, whatever layout the padded COO has: what
    ``reference_batch``, which is handed the graph function, judges."""
    if "hops" in batch:
        sets = [h["gids"] for h in batch["hops"]]
        coo = [(a["src"], a["dst"], a["mask"]) for a in batch["adjs"]]
        over = 0
    else:
        sets, coo, over = _expansion_fn(model.module)(batch, state["consts"])
    sets = [np.asarray(s) for s in jax.device_get(sets)]
    _run["overflow"].append(int(over))
    edges = []
    for h, (src, dst, mask) in enumerate(jax.device_get(coo)):
        live = np.asarray(mask).reshape(-1) > 0
        edges += [np.asarray(src).reshape(-1)[live],
                  sets[h + 1][np.asarray(dst).reshape(-1)[live]],
                  np.array([live.size])]
    return sets + edges


def reference_batch(spec, hops: list) -> dict:
    """What the reference trains on: the step's ROOTS and the graph
    function; it expands them itself (``expand``). Here, where the graph
    function is handed over, the program's expansion of the step is
    judged against it, edge by edge; what is off rides the REFERENCE's
    leaf ``expansion_off`` (``train_steps``), against a program's leaf of
    nought."""
    levels = (len(hops) - 1) // 4
    sets, edges = hops[:levels + 1], hops[levels + 1:]
    off, said = {c: 0 for c in COUNTS}, []
    for h in range(levels):
        pos, child, slots = edges[3 * h:3 * h + 3]
        j = judge_hop(spec, sets[h], sets[h + 1], pos, child)
        for c in COUNTS:
            off[c] += j[c]
        said.append("hop %d: %d nodes, %d edges (%d a second time) in %d "
                    "slots" % (h + 1, j["set"], j["edges"],
                               j["listed_twice"], int(slots[0])))
    log.info(
        "gcn reference: a step's expansion against the graph function: "
        "%s; %s", ", ".join("%s %d" % (c, off[c]) for c in COUNTS),
        "; ".join(said))
    return {"spec": spec, "off": sum(off.values()),
            "roots": np.asarray(hops[0], np.int64).reshape(-1)}


def batch_rows(cfg: dict, batch: dict, rows: int) -> dict:
    """The batch of the first ``rows`` roots."""
    return dict(batch, roots=batch["roots"][:rows])
