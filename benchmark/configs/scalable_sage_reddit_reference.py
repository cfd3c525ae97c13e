"""Plain reference and adapter of one ScalableSage training step: the
historical-store family of the upstream project (alibaba/euler
``tf_euler/python/models/graphsage.py`` ScalableSage,
``encoders.py:404-519`` ScalableSageEncoder and the store hooks of
``encoders.py:218-519``; ``run_loop.py --model scalable_sage``), at the
Reddit recipe's widths. Straightforward ``jax.numpy`` in float32 at
``highest`` matmul precision with the stores' bookkeeping in numpy;
imports nothing of the program and nothing of the tests' toy family.

The model is supervised GraphSAGE with two mean-aggregator layers
(concat) and a linear classifier, but ONE drawn hop: layer 1 reads its
neighbours' layer-0 embeddings from a per-node ``store`` of past steps
instead of drawing a second hop, and the gradient that reaches those stale
rows is parked in a per-node ``grad_store`` until the node is next a
root. One step on roots ``r`` [B] and their drawn neighbours ``n``
[B*f]:

1. ``stale = grad_store[r]``; then those rows are cleared;
2. layer 0 on the roots' and neighbours' features gives ``h0`` [B, dim];
   layer 1 on ``h0`` and the stale rows ``store[n]`` gives the logits;
   the loss (softmax cross-entropy, mean over the batch) and its
   gradients by the parameters and by the rows read; Adam
   (``learning_rate``) moves the parameters;
3. the store loss ``sum(h0 * stale)``: its gradient by the parameters,
   taken at the parameters as they were before step 2, goes to a second
   Adam (``store_learning_rate``) whose step is added to the parameters
   the first has already moved;
4. ``grad_store[n] += d(loss + store loss)/d(store[n])`` (scatter-add:
   a neighbour drawn several times collects every share);
5. ``store[r] = h0``.

Departures of the program from the published description, each noted:

* upstream splits 1, 4 and 5 over three TensorFlow session hooks around
  ``sess.run(train_op)``; the program fuses all five into one jitted
  step. The order above is what both compute: 1 before 2 (the clear
  comes before the add of 4, so a root that is also a neighbour of its
  own batch ends the step holding this step's share alone), the reads
  of 2 before the write of 5 (a neighbour that is also a root of the
  same batch is read stale);
* a root drawn k times into one batch has k fresh rows ``h0`` (its
  neighbours are drawn anew each time) and upstream's ``scatter_update``
  leaves open which one stays. The program keeps the LAST occurrence in
  batch order and drops the others, whatever order the device writes
  in; this reference does the same. Its stale gradient is read k times
  (step 3 counts it once per occurrence), as upstream's gather does.

The adapter's side (the protocol is stated in
``benchmark/sage_reference.py``): the program's step takes ``params``,
``opt_state``, ``stores``, ``grad_stores`` and ``store_opt_state``.

What the steps start from. The weights and the store's uniform start
come from the key, as the program's own ``init_state`` makes them. The
gradient store does NOT start at nought: under uniform roots over
2,090,000 nodes the three captured steps share two to five rows, so a
gradient store that starts empty hands every root a stale gradient of
nought, the store loss is nought, the second Adam has no work, and a
step that never clears a row or skips that Adam reads the same as a
sound one. It starts as a deployment holds it once the run is under
way: every row filled, normal with ``grad_store_init_std`` an entry
(the configuration's file says where the number comes from). Then each
of the 1,000 roots of a step reads a stale gradient, the store loss and
the second Adam work at the size of the first, and a clear that is not
done leaves 1,000 rows standing.

Compared after step 1: the loss's gradient as the first Adam got it.
Compared after step 3, as changes from ``start``: the six parameters;
both Adams' first moments (``mu/<leaf>``, and ``store_mu/<leaf>``: the
store loss's gradients, which at two layers reach layer 0 alone);
``store0/rows`` and ``grad_store0/rows``, the rows of the two tables
that the captured steps named (roots and neighbours, each once, in id
order); ``store0/twice``, the store rows of the roots that one step drew
more than once, as they stand (one row among 3,000 is lost in a norm;
alone, the candidate that was not to stay reads a norm of its own); and
``rows_outside_changed``, the exact statement about the rest of the two
tables: the number of rows no captured step named that are not
bit-identical to their start. The reference's is nought, so one such
row reads a gap of one over the median leaf's norm, far over any limit.

``FAULTS`` are the store mechanism's planted faults: the same reference
with one rule broken, to be put in the program's place as the control
is (``scalable_sage_reddit_faults.py`` does, on the chip). A sound
implementation never takes those branches.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark.sage_reference import (  # noqa: F401
    adam_init,
    adam_update,
    first_gradient,  # the first Adam and the parameter tree are GraphSAGE's
    from_program,
    init_params,
    to_program,
)

log = logging.getLogger("benchmark")

MU, STORE_MU = "mu/", "store_mu/"
ROWS, GRAD_ROWS, TWICE = "store0/rows", "grad_store0/rows", "store0/twice"
OUTSIDE = "rows_outside_changed"
# the leaves of ``start`` that are no parameter, by the head of their name
AUXILIARY = ("mu", "store_mu", "store0", "grad_store0", OUTSIDE)
# one rule of the store mechanism broken, by name (``follow``)
FAULTS = ("store_write_skipped", "stale_gradient_not_cleared",
          "second_adam_skipped", "reads_fresh", "first_duplicate_kept")

# What the adapter remembers of the train() call in progress: the
# protocol hands ``compared_state`` the program's state alone, and the
# row leaves need the two tables' start and the ids of every captured
# step. Set by ``init_state``, filled by ``drawn_hops``.
_run = {"start": None, "hops": []}


def _layer(self_x, neigh_mean, w_self, w_neigh, act, precision):
    a = jnp.dot(self_x, w_self, precision=precision)
    b = jnp.dot(neigh_mean, w_neigh, precision=precision)
    if act:
        a, b = jax.nn.relu(a), jax.nn.relu(b)
    return jnp.concatenate([a, b], axis=1)


def forward(params, x0, x1, reads, labels, cfg, precision="highest"):
    """(loss, h0): x0 [B, F] roots' features, x1 [B*f, F] neighbours',
    reads [B*f, dim] the store rows of the neighbours, labels [B, C]."""
    fan = cfg["fanouts"][0]
    b = x0.shape[0]
    h0 = _layer(x0, x1.reshape(b, fan, -1).mean(axis=1),
                params["w_self0"], params["w_neigh0"], True, precision)
    z = _layer(h0, reads.reshape(b, fan, -1).mean(axis=1),
               params["w_self1"], params["w_neigh1"], False, precision)
    logits = jnp.dot(z, params["w_out"], precision=precision) + params["b_out"]
    if cfg["sigmoid_loss"]:
        per = (jnp.maximum(logits, 0) - logits * labels
               + jnp.log1p(jnp.exp(-jnp.abs(logits))))
        return per.mean(), h0
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -(labels * logp).sum(axis=-1).mean(), h0


@functools.lru_cache(maxsize=16)
def _gradients(fan: int, sigmoid_loss: bool, dtype, precision):
    """Parts 2 and 3 of a step, jitted once per (shape of the model,
    type, precision): the calibration follows many seeds."""
    cfg = {"fanouts": [fan], "sigmoid_loss": sigmoid_loss}

    def grads(p, x0, x1, reads, y, stale):
        p, x0, x1, reads, y, stale = jax.tree_util.tree_map(
            lambda a: a.astype(dtype), (p, x0, x1, reads, y, stale))

        def loss_fn(p, reads):
            return forward(p, x0, x1, reads, y, cfg, precision)

        def store_loss_fn(p, reads):
            _, h0 = forward(p, x0, x1, reads, y, cfg, precision)
            return jnp.sum(h0 * stale)

        (loss, h0), (gp, gr) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(p, reads)
        gp_s, gr_s = jax.grad(store_loss_fn, argnums=(0, 1))(p, reads)
        return jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), (loss, h0, gp, gr, gp_s, gr_s))

    return jax.jit(grads)


def last_occurrence(ids: np.ndarray) -> np.ndarray:
    """Positions of the last row holding each distinct id."""
    _, first_from_the_end = np.unique(ids[::-1], return_index=True)
    return len(ids) - 1 - first_from_the_end


def follow(cfg: dict, start: dict, batches: list, dtype=jnp.float32,
           precision="highest", fault=None):
    """The steps themselves, one result a step: (loss, the loss's
    gradient by the parameters, parameters, store, gradient store, first
    Adam, second Adam) as they stand after it. The two tables are the
    same arrays from step to step (copy what is kept). ``fault``: one of
    ``FAULTS``, for a calibration."""
    assert fault is None or fault in FAULTS, fault
    names = [k for k in start if k.split("/")[0] not in AUXILIARY]
    step = _gradients(cfg["fanouts"][0], bool(cfg["sigmoid_loss"]),
                      dtype, precision)
    params = {k: jnp.asarray(start[k]) for k in names}
    store = np.array(start["store0"], dtype=np.float32)
    grad_store = np.array(start["grad_store0"], dtype=np.float32)
    opt, store_opt = adam_init(params), adam_init(params)
    opt["m"] = {k: jnp.asarray(start[MU + k]) for k in names}
    store_opt["m"] = {k: jnp.asarray(start[STORE_MU + k]) for k in names}
    for batch in batches:
        r, n = batch["roots"], batch["neighbours"]
        keep = last_occurrence(r)
        stale = grad_store[r]                                      # 1
        if fault != "stale_gradient_not_cleared":
            grad_store[r] = 0.0
        reads = store[n]
        if fault == "reads_fresh":
            # layer 1 reads after part 5: a neighbour that is a root of
            # this batch is read as this step writes it (layer 0, and so
            # the row written, does not depend on what layer 1 reads)
            h0 = np.asarray(step(params, batch["x0"], batch["x1"], reads,
                                 batch["y"], stale)[1])
            written = {int(r[i]): h0[i] for i in keep}
            for i, node in enumerate(n.tolist()):
                if node in written:
                    reads[i] = written[node]
        loss, h0, gp, gr, gp_s, gr_s = step(
            params, batch["x0"], batch["x1"], reads, batch["y"], stale)
        moved, opt = adam_update(params, gp, opt, cfg["learning_rate"])  # 2
        if fault == "second_adam_skipped":
            params = moved
        else:
            delta, store_opt = adam_update(                        # 3
                params, gp_s, store_opt, cfg["store_learning_rate"])
            params = {k: moved[k] + (delta[k] - params[k]) for k in names}
        np.add.at(grad_store, n, np.asarray(gr) + np.asarray(gr_s))  # 4
        if fault == "first_duplicate_kept":
            keep = np.unique(r, return_index=True)[1]
        if fault != "store_write_skipped":                         # 5
            store[r[keep]] = np.asarray(h0)[keep]
        yield float(loss), gp, params, store, grad_store, opt, store_opt


def row_leaves(store, grad_store, start: dict, steps: list) -> dict:
    """The compared rows of the two tables after ``steps`` (a (roots,
    neighbours) pair each): what the steps named, as changes from their
    start; the roots one step drew more than once, as they stand; and
    the count of rows outside the steps that are not bit-identical to
    their start."""
    named = np.unique(np.concatenate([ids for s in steps for ids in s]))
    twice = []
    for roots, _ in steps:
        ids, count = np.unique(roots, return_counts=True)
        twice.append(ids[count > 1])
    twice = np.unique(np.concatenate(twice))
    store0, grad_store0 = start["store0"], start["grad_store0"]
    outside = np.ones(len(store), bool)
    outside[named] = False
    bits = np.uint32
    changed = sum(
        int(((now.view(bits) != was.view(bits)).any(axis=1) & outside).sum())
        for now, was in ((store, store0), (grad_store, grad_store0)))
    return {
        ROWS: store[named] - store0[named],
        GRAD_ROWS: grad_store[named] - grad_store0[named],
        TWICE: store[twice],
        OUTSIDE: np.array([changed], np.float32),
    }


def train_steps(cfg: dict, start: dict, batches: list, dtype=jnp.float32,
                precision="highest", fault=None):
    """Follow ``len(batches)`` steps from ``start``. Returns (losses, the
    loss's first gradient by the parameters, the compared leaves after
    the last step). ``precision=None``: the same float32 step at the
    platform's default matmul precision. ``dtype`` bfloat16 is the
    control: parameters, features, store rows, activations and gradients
    in bfloat16; master weights, stores and both Adams in float32."""
    # the comparison hands ``start`` over as device arrays: the tables
    # come back to the host once
    start = dict(start, **{k: np.asarray(start[k])
                           for k in ("store0", "grad_store0")})
    steps = list(follow(cfg, start, batches, dtype, precision, fault))
    _, _, params, store, grad_store, opt, store_opt = steps[-1]
    end = dict(params)
    end.update({MU + k: v for k, v in opt["m"].items()})
    end.update({STORE_MU + k: v for k, v in store_opt["m"].items()})
    end.update(row_leaves(store, grad_store, start, [
        (b["roots"], b["neighbours"]) for b in batches]))
    return [s[0] for s in steps], steps[0][1], end


# ---- adapter ----

def init_state(cfg: dict, key, optimizer) -> tuple:
    """(start, state): weights, the store's uniform start and the
    gradient store's fill from ``key`` in one jitted call; the program's
    state but ``consts``. The tables of ``start`` are host arrays, so
    that nothing of ``start`` stays on the device through the window."""
    rows = cfg["graph"]["num_nodes"] + 1   # the program's max_id + 2
    shape = (rows, cfg["dim"])

    def make(k):
        p = init_params(cfg, k)
        p["store0"] = jax.random.uniform(
            jax.random.fold_in(k, 1000), shape, jnp.float32,
            0.0, cfg["store_init_maxval"])
        p["grad_store0"] = cfg["grad_store_init_std"] * jax.random.normal(
            jax.random.fold_in(k, 1001), shape, jnp.float32)
        return p

    start = jax.jit(make)(key)
    store0, grad_store0 = start.pop("store0"), start.pop("grad_store0")
    tree = to_program(start)
    state = {
        "params": tree,
        "opt_state": optimizer.init(tree),
        "stores": [store0],
        "grad_stores": [grad_store0],
        "store_opt_state": optax.adam(cfg["store_learning_rate"]).init(tree),
    }
    start.update({pre + k: jnp.zeros_like(v)
                  for k, v in list(start.items()) for pre in (MU, STORE_MU)})
    start["store0"] = np.asarray(store0)
    start["grad_store0"] = np.asarray(grad_store0)
    # the row leaves are changes already (or rows as they stand)
    start.update({k: np.zeros(1, np.float32)
                  for k in (ROWS, GRAD_ROWS, TWICE, OUTSIDE)})
    _run.update(start=start, hops=[])
    return start, state


def compared_state(state) -> dict:
    got = jax.device_get({k: state[k] for k in (
        "params", "opt_state", "stores", "grad_stores", "store_opt_state")})
    out = {k: np.asarray(v) for k, v in from_program(got["params"]).items()}
    for pre, opt_state in ((MU, got["opt_state"]),
                           (STORE_MU, got["store_opt_state"])):
        mu = opt_state[0].mu
        out.update({pre + k: np.asarray(v)
                    for k, v in from_program(mu).items()})
    out.update(row_leaves(
        np.asarray(got["stores"][0]), np.asarray(got["grad_stores"][0]),
        _run["start"], _run["hops"]))
    log.info(
        "store reference: %d rows outside the %d captured steps changed; "
        "%d roots were drawn more than once in a step (the last one's row "
        "is kept)", out[OUTSIDE][0], len(_run["hops"]), len(out[TWICE]))
    return out


def drawn_fanouts(cfg: dict) -> list:
    """``fanouts`` counts layers here: one hop is drawn, at its first."""
    return [cfg["fanouts"][0]]


@functools.lru_cache(maxsize=8)
def _expand_fn(model):
    def expand(batch, consts):
        out = model._expand_batch(batch, consts)
        return out["node_ids"], out["neigh_ids"]

    return jax.jit(expand)


def drawn_hops(model, state, batch) -> list:
    """[roots, neighbours] of this step. A host-sampled batch carries
    them; a device-sampled one (roots + seed) is expanded by the model's
    own entry, as inside its step (the same key derivation, routing and
    kernel), jitted alone."""
    if "node_ids" in batch:
        hops = [batch["node_ids"], batch["neigh_ids"]]
    else:
        hops = list(_expand_fn(model)(batch, state["consts"]))
    _run["hops"].append(tuple(
        np.asarray(jax.device_get(h), dtype=np.int64).reshape(-1)
        for h in hops))
    return hops


def reference_batch(spec, hops: list) -> dict:
    roots, neigh = (np.asarray(h, dtype=np.int64).reshape(-1) for h in hops)
    return {"roots": roots, "neighbours": neigh,
            "x0": spec.features(roots), "x1": spec.features(neigh),
            "y": spec.labels(roots)}


def batch_rows(cfg: dict, batch: dict, rows: int) -> dict:
    """The batch of the first ``rows`` roots, with their draws."""
    fan = cfg["fanouts"][0]
    return {k: v[:rows * (fan if k in ("neighbours", "x1") else 1)]
            for k, v in batch.items()}
