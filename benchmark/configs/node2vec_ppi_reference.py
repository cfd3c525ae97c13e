"""Plain reference and adapter of one node2vec training step: the
walk-and-embedding family of the upstream project (alibaba/euler
``tf_euler/python/models/node2vec.py``, ``run_loop.py --model node2vec``
under its own flag defaults, the pair enumeration of
``tf_euler/kernels/gen_pair_op.cc:43-95`` and the decoder of
``models/base.py:82-95``). Straightforward ``jax.numpy`` in float32 at
``highest`` matmul precision for the pair loss and its gradients by the
gathered rows; the rows gathered by plain indexing, their gradients
added into numpy arrays by ``np.add.at``, Adam written out. Imports
nothing of the program.

One step on ``B`` roots:

1. a walk of ``walk_len`` uniform single-neighbour draws from each root
   (``walk_p = walk_q = 1``): ``paths`` [B, walk_len + 1];
2. skip-gram pairs by the window rule: for every position ``j`` of a
   path its left contexts ``j-1, j-2, ...`` (at most ``left_win_size``)
   and then its right contexts ``j+1, j+2, ...`` (at most
   ``right_win_size``), in that order: ``P`` pairs a root (30 at the
   defaults), ``src`` and ``pos`` [B*P];
3. ``num_negs`` negatives a pair from the global node sampler: ``negs``
   [B*P*num_negs], the first ``num_negs`` belonging to the first pair;
4. the rows ``target[src]``, ``context[pos]``, ``context[negs]`` of two
   id-embedding tables of ``num_nodes + 1`` rows and ``dim`` columns;
   logits are the rows' dot products; the loss is the SUM over pairs of
   sigmoid cross-entropy against 1 for the positive and against 0 for
   each negative (``xent_loss``);
5. the two dense gradients: every gathered row's gradient is ADDED into
   its table's row (a node named by several pairs collects every share:
   30 pairs share 6 nodes);
6. Adam (b1 0.9, b2 0.999, eps 1e-8) on both whole tables. Upstream's
   TensorFlow 1 ``AdamOptimizer`` decays ``m`` and ``v`` of EVERY row at
   every step and moves every row by them, also the rows this step's
   gradient did not name: a row named at step 1 keeps moving at steps 2
   and 3. A lazy, row-wise Adam is a different result.

Why the reference holds rows and no table. A row that no step so far has
named has a gradient of nought, moments of nought and therefore an Adam
update of exactly nought (0 / (sqrt(0) + eps)): it stands as it began.
So the dense recipe restricted to the rows the followed steps name IS
the dense recipe, and the reference keeps those rows alone (a few
hundred thousand of a million). The statement about all other rows is
exact and is checked on the program's side, on the device: the count of
rows outside the named ones whose table bits differ from their start, or
whose first moment is not nought (``rows_outside_changed``; the
reference's is 0, so one such row reads a gap of one over the median
leaf's norm). For that, a table's start is a function of (key, row id)
alone (``init_rows``), so any row's start can be made again anywhere
without keeping a second copy of 2 x 1 GB.

The adapter's side (the protocol is stated in
``benchmark/sage_reference.py``). The program's step takes ``params``
(``target`` and ``context``, an ``Embedding_0/embeddings`` each) and
``opt_state``. The ids of a step come from the module's own ``_inputs``
(same key derivation, routing and kernel as inside the step), jitted
alone on the step's batch: it returns the pairs and the negatives; the
walk is read back out of ``src`` (every position of a path is the
target of some pair), and the pairs are then made again here by the rule
above, so a program that pairs otherwise reads another loss. ``hops`` is
the walk's columns, root first, then the negatives: ``check.py`` judges
the walk's steps (each id a neighbour of its predecessor in the graph
function: ``draw_foreign``, ``draw_skew``) over ``drawn_fanouts`` = five
hops of one.

Compared after step 1: the first gradient as Adam got it (``mu`` over
``1 - b1``) on the rows step 1 named: ``target/walk``, ``context/walk``,
``context/negs``. Compared after step 3, as changes from the start: the
same three sets of rows over all three steps, of both tables
(``target/walk``, ``context/walk``, ``context/negs``) and of both first
moments (``mu_target/walk``, ...); ``mu_context/twice``, the first
moment of the rows one step drew as a negative more than once (where a
scatter that overwrites keeps one share of two);
``rows_outside_changed``; and ``negs_off_sampler``. Never a whole table
to the host.

The negatives are judged on their own, not against the program: the walk
and the pairs are made again here, but the negatives of a step are the
program's own draw, handed to both sides, so every gap would read 0 on
negatives that came from anywhere. ``sampler_z`` holds each step's
negatives against what independent uniform draws over the graph
function's nodes (unit weights) give: the id range, the mean quantile,
the count of distinct ids (a stuck sampler draws few, a permutation too
many) and the count that are ids of the step's own walks (negatives made
from the pairs are all of them), each in standard deviations of its
count under such a sampler. ``negs_off_sampler`` counts the ids out of
range and the statistics past the configuration's ``negs_z_limit``; the
reference's is 0, so one reads a gap of one over the median leaf's norm.

At load the adapter looks once at the program it stands beside, where
there is one (``refuse_program_without_walk_scopes``): a program from
before the cell is refused there, with exit code 1 and no result.

``FAULTS`` are the family's planted faults: this reference with one rule
broken, put in the program's place as the control is
(``scalable_sage_reddit_faults.py --workload node2vec_device_train``
does, on the chip). A sound implementation never takes those branches.
"""

from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
import numpy as np

log = logging.getLogger("benchmark")

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
INIT_STDDEV = 0.1      # the program's Embedding: truncated normal, 2 sigma
TABLES = ("target", "context")
WALK, NEGS = "/walk", "/negs"
TWICE, OUTSIDE = "mu_context/twice", "rows_outside_changed"
SAMPLER = "negs_off_sampler"
# rows of a table compared against their start in one go, on the device
BLOCK_ROWS = 1 << 16
# one rule of the family broken, by name (``follow``)
FAULTS = ("duplicates_overwritten", "context_table_is_target",
          "negatives_from_pairs", "window_one_sided", "adam_rows_only")

# What the adapter remembers of the train() call in progress: the
# protocol hands ``first_gradient`` and ``compared_state`` the program's
# state alone, and the row leaves need the key the tables were made from
# and the ids of every captured step. Set by ``init_state``, filled by
# ``drawn_hops``.
_run = {"cfg": None, "key": None, "hops": []}

# The device scopes the cell's four per-layer metrics read
# (``layers/walk.scope_ms.py``, ``embed.pair_rows_ms.py`` and the two
# rooflines), as the program lists them in ``trace.STEP_SCOPES``.
WALK_SCOPES = ("walk", "negatives", "pair_rows")


def refuse_program_without_walk_scopes() -> None:
    """End the run at once, exit code 1 and no result line, where the
    program that is loaded beside this file lists no walk scopes.

    Such a program is one from before the cell. It has ``--model
    node2vec`` and would reach a result line, ``correct`` true, but its
    per-hop draw kernel unrolls the 512 row copies of a walk step's
    stage: 290 s of Python tracing a run, 366 to 459 s a run on the
    chip (PERF.md section 6, PR 35), over the 360 s at which the
    driver's check stops a run (it stopped the parent's run there and
    refused PR 35 for it; PR 34 was refused on the same side as
    ``process_left_running``). Nothing under
    ``benchmark/`` can take that kernel off its path, so the cell says
    at load, some 30 s into the run and before a model is built or a
    step traced, that this program cannot run it.

    It leaves by ``os._exit`` as ``run.py`` does after a result, and for
    its reason: by now the TPU client is open, the memory sampler's
    thread ticks and the engine's teams stand, and ``run.py`` lets any
    exception but ``NoChip`` unwind through the interpreter's ordinary
    shutdown, where those may hold the exit. The reference used alone
    (no program loaded) is held to nothing."""
    import importlib
    import os
    import sys

    if "euler_tpu" not in sys.modules:
        return
    # the harness has the program loaded by now, but not this module of
    # it (run_loop takes it in lazily): ask for it by name
    trace = importlib.import_module("euler_tpu.trace")
    lacks = [s for s in WALK_SCOPES
             if s not in getattr(trace, "STEP_SCOPES", ())]
    if not lacks:
        return
    print(
        "node2vec reference: the program's trace.STEP_SCOPES lacks "
        f"{', '.join(lacks)}: a program from before the walk cell, whose "
        "per-hop draw kernel unrolls a walk step's stage (about 290 s of "
        "tracing a run, past the time a run of the check may take) and "
        "whose step gives the cell's per-layer metrics nothing to read; "
        "this cell cannot run on it; no result",
        file=sys.stderr)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(1)


refuse_program_without_walk_scopes()


# ---- the recipe's bookkeeping ----

def pair_positions(path_len: int, left_win: int, right_win: int) -> tuple:
    """(target positions, context positions) of one path's pairs, in the
    order of upstream's ``gen_pair`` kernel."""
    tgt, ctx = [], []
    for j in range(path_len):
        for k in range(1, left_win + 1):
            if j - k >= 0:
                tgt.append(j)
                ctx.append(j - k)
        for k in range(1, right_win + 1):
            if j + k < path_len:
                tgt.append(j)
                ctx.append(j + k)
    return np.array(tgt, np.int64), np.array(ctx, np.int64)


def windows(cfg: dict) -> tuple:
    return int(cfg["left_win_size"]), int(cfg["right_win_size"])


def pairs_of(cfg: dict, paths: np.ndarray, left=None) -> tuple:
    """(src, pos) [B*P] of the walks ``paths`` [B, walk_len + 1];
    ``left`` overrides the left window (a planted fault's)."""
    lw, rw = windows(cfg)
    tgt, ctx = pair_positions(
        paths.shape[1], lw if left is None else left, rw)
    return paths[:, tgt].reshape(-1), paths[:, ctx].reshape(-1)


@functools.partial(jax.jit, static_argnums=0)
def init_rows(dim: int, key, ids):
    """Rows ``ids`` of a table as it starts: each row a function of
    (key, row id), truncated normal at two sigma times ``INIT_STDDEV``
    (the program's own ``nn.Embedding`` start)."""
    def row(i):
        return jax.random.truncated_normal(
            jax.random.fold_in(key, i), -2.0, 2.0, (dim,), jnp.float32)

    return INIT_STDDEV * jax.vmap(row)(jnp.asarray(ids, jnp.int32))


def table_key(key, table: str):
    return jax.random.fold_in(key, TABLES.index(table))


def pair_loss(src_rows, pos_rows, neg_rows, precision="highest"):
    """Sum over pairs of sigmoid cross-entropy: the positive's logit
    against 1, each negative's against 0. src_rows, pos_rows [P, d];
    neg_rows [P, K, d]."""
    pos = jnp.einsum("pd,pd->p", src_rows, pos_rows, precision=precision)
    neg = jnp.einsum("pd,pkd->pk", src_rows, neg_rows, precision=precision)

    def xent(x, y):
        # max(x, 0) - x*y + log(1 + exp(-|x|))
        return jnp.maximum(x, 0) - x * y + jnp.log1p(jnp.exp(-jnp.abs(x)))

    return xent(pos, 1.0).sum() + xent(neg, 0.0).sum()


@functools.lru_cache(maxsize=16)
def _gradients(dtype, precision):
    """Loss and its gradients by the three sets of gathered rows, jitted
    once per (type, precision): a calibration follows many seeds."""
    def grads(src_rows, pos_rows, neg_rows):
        rows = tuple(a.astype(dtype) for a in (src_rows, pos_rows, neg_rows))
        loss, g = jax.value_and_grad(
            lambda r: pair_loss(*r, precision=precision))(rows)
        return loss.astype(jnp.float32), tuple(
            a.astype(jnp.float32) for a in g)

    return jax.jit(grads)


@jax.jit
def adam_rows(p, g, m, v, t, lr, rows=None):
    """One dense Adam step on float32 [n, d] arrays: (p, m, v) after it.
    ``rows`` (an [n] mask) moves only those rows and their moments (the
    lazy update upstream does NOT do)."""
    m1 = ADAM_B1 * m + (1 - ADAM_B1) * g
    v1 = ADAM_B2 * v + (1 - ADAM_B2) * g ** 2
    mhat = m1 / (1 - ADAM_B1 ** t)
    vhat = v1 / (1 - ADAM_B2 ** t)
    p1 = p - lr * mhat / (jnp.sqrt(vhat) + ADAM_EPS)
    if rows is None:
        return p1, m1, v1
    keep = rows[:, None]
    return (jnp.where(keep, p1, p), jnp.where(keep, m1, m),
            jnp.where(keep, v1, v))


def named_ids(batches: list) -> tuple:
    """(walk ids, negative ids) the steps named, each once, in id order."""
    walk = np.unique(np.concatenate([b["paths"].reshape(-1)
                                     for b in batches]))
    negs = np.unique(np.concatenate([b["negs"] for b in batches]))
    return walk, negs


def twice_ids(batches: list) -> np.ndarray:
    """Ids one step drew as a negative more than once."""
    out = []
    for b in batches:
        ids, count = np.unique(b["negs"], return_counts=True)
        out.append(ids[count > 1])
    return np.unique(np.concatenate(out))


def step_ids(cfg: dict, batch: dict, fault=None) -> tuple:
    """(src, pos, negatives) of one step: the pairs of its walks by the
    window rule and the negatives it drew."""
    k = int(cfg["num_negs"])
    if fault == "window_one_sided":
        src, pos = pairs_of(cfg, batch["paths"], left=0)
        both = len(pairs_of(cfg, batch["paths"][:1])[0])
        # the negatives of the pairs that stay: the first of a root's
        neg = batch["negs"].reshape(len(batch["paths"]), both, k)
        neg = neg[:, :len(src) // len(batch["paths"])].reshape(-1)
    else:
        src, pos = pairs_of(cfg, batch["paths"])
        neg = batch["negs"]
    if fault == "negatives_from_pairs":
        # no draw from the sampler: a pair's negatives are the contexts
        # of the pairs after it
        neg = np.stack([np.roll(pos, -(i + 1)) for i in range(k)],
                       axis=1).reshape(-1)
    return src, pos, neg


def sampler_z(num_nodes: int, paths, negs) -> dict:
    """One step's negatives against ``len(negs)`` independent uniform
    draws over the ids ``0 .. num_nodes - 1``: ``foreign``, the count out
    of that range, and three counts of the others in standard deviations
    from their mean under such a sampler. ``skew``: the mean quantile
    ``(id + 0.5) / num_nodes`` (0.5, variance 1 / 12n). ``distinct``: the
    ids drawn at least once (n draws leave a node out with probability
    q1 = (1 - 1/N)^n, two nodes with q2 = (1 - 2/N)^n: mean N(1 - q1),
    variance N q1 + N(N - 1) q2 - (N q1)^2). ``walk``: the negatives that
    are one of the step's w distinct walk ids (binomial, n draws at
    w / N)."""
    negs = np.asarray(negs, np.int64).reshape(-1)
    big = float(num_nodes)
    inside = negs[(negs >= 0) & (negs < num_nodes)]
    n = len(inside)
    out = {"foreign": float(len(negs) - n), "skew": 0.0, "distinct": 0.0,
           "walk": 0.0}
    if n == 0:
        return out
    out["skew"] = float(((inside + 0.5) / big).mean() - 0.5) \
        * math.sqrt(12 * n)
    q1 = math.exp(n * math.log1p(-1 / big))
    q2 = math.exp(n * math.log1p(-2 / big))
    var = big * q1 + big * (big - 1) * q2 - (big * q1) ** 2
    out["distinct"] = (len(np.unique(inside)) - big * (1 - q1)) \
        / math.sqrt(max(var, 1e-9))
    share = len(np.unique(paths)) / big
    out["walk"] = (float(np.isin(inside, paths).sum()) - n * share) \
        / math.sqrt(max(n * share * (1 - share), 1e-9))
    return out


def off_sampler(cfg: dict, batches: list) -> np.ndarray:
    """[1]: over the steps, the negatives out of the id range and the
    statistics of ``sampler_z`` further than ``negs_z_limit`` from what
    the global node sampler gives."""
    limit, count, worst = float(cfg["negs_z_limit"]), 0.0, {}
    for b in batches:
        z = sampler_z(cfg["graph"]["num_nodes"], b["paths"], b["negs"])
        count += z.pop("foreign")
        count += sum(abs(v) > limit for v in z.values())
        worst = {k: max(abs(v), worst.get(k, 0.0)) for k, v in z.items()}
    log.info(
        "node2vec reference: negatives against the node sampler over %d "
        "steps: %d off (limit %g); largest |z| skew %.3f distinct %.3f "
        "walk %.3f", len(batches), count, limit, worst.get("skew", 0.0),
        worst.get("distinct", 0.0), worst.get("walk", 0.0))
    return np.array([count], np.float32)


def follow(cfg: dict, key, batches: list, dtype=jnp.float32,
           precision="highest", fault=None):
    """The steps themselves on the rows ``batches`` name. Yields a step's
    (loss, gradient rows {table: [n, d]}, rows {table: ...}, first
    moments, second moments, named ids [n]) as they stand after it. The
    rows are gathered by plain indexing, the gradient shares are added
    into numpy arrays by ``np.add.at``, Adam is written out
    (``adam_rows``)."""
    assert fault is None or fault in FAULTS, fault
    walk, negs = named_ids(batches)
    named = np.union1d(walk, negs)
    p = {t: init_rows(cfg["dim"], table_key(key, t), named) for t in TABLES}
    m = {t: jnp.zeros_like(p[t]) for t in TABLES}
    v = {t: jnp.zeros_like(p[t]) for t in TABLES}
    step = _gradients(dtype, precision)
    k = int(cfg["num_negs"])
    for t, batch in enumerate(batches, 1):
        src, pos, neg = step_ids(cfg, batch, fault)
        ls, lp, ln = (np.searchsorted(named, a) for a in (src, pos, neg))
        ctx = "target" if fault == "context_table_is_target" else "context"
        loss, (g_src, g_pos, g_neg) = step(
            p["target"][ls], p[ctx][lp],
            p[ctx][ln].reshape(len(ls), k, -1))
        g = {tb: np.zeros(p[tb].shape, np.float32) for tb in TABLES}
        shares = (("target", ls, np.asarray(g_src)),
                  (ctx, lp, np.asarray(g_pos)),
                  (ctx, ln, np.asarray(g_neg).reshape(len(ln), -1)))
        for tb, rows, share in shares:
            if fault == "duplicates_overwritten":
                g[tb][rows] = share          # a set where the step adds
            else:
                np.add.at(g[tb], rows, share)
        for tb in TABLES:
            touched = g[tb].any(axis=1) if fault == "adam_rows_only" \
                else None
            p[tb], m[tb], v[tb] = adam_rows(
                p[tb], g[tb], m[tb], v[tb], t, cfg["learning_rate"],
                touched)
        yield float(loss), g, dict(p), dict(m), dict(v), named


def _rows(named, ids, table):
    return np.asarray(table[np.searchsorted(named, ids)])


def train_steps(cfg: dict, start: dict, batches: list, dtype=jnp.float32,
                precision="highest", fault=None):
    """Follow ``len(batches)`` steps from the tables ``start["key"]``
    makes. Returns (losses, the first gradient's row leaves, the compared
    leaves after the last step, as changes from the start).
    ``precision=None``: the same float32 step at the platform's default
    matmul precision. ``dtype`` bfloat16 is the control: gathered rows,
    logits, loss and gradients in bfloat16; tables and Adam in float32."""
    key = jnp.asarray(start["key"], jnp.uint32)
    walk1, negs1 = named_ids(batches[:1])
    walk, negs = named_ids(batches)
    twice = twice_ids(batches)
    losses, first = [], None
    for loss, g, p, m, v, named in follow(
            cfg, key, batches, dtype, precision, fault):
        losses.append(loss)
        if first is None:
            first = {"target" + WALK: _rows(named, walk1, g["target"]),
                     "context" + WALK: _rows(named, walk1, g["context"]),
                     "context" + NEGS: _rows(named, negs1, g["context"])}
    p0 = {t: init_rows(cfg["dim"], table_key(key, t), named) for t in TABLES}
    end = {}
    for t, ids, tail in (("target", walk, WALK), ("context", walk, WALK),
                         ("context", negs, NEGS)):
        end[t + tail] = _rows(named, ids, p[t]) - _rows(named, ids, p0[t])
        end["mu_" + t + tail] = _rows(named, ids, m[t])
    end[TWICE] = _rows(named, twice, m["context"])
    end[OUTSIDE] = np.zeros(1, np.float32)
    # the sampler's own negatives read nought; a fault's are judged as
    # the program's are
    end[SAMPLER] = np.zeros(1, np.float32) if fault is None else off_sampler(
        cfg, [dict(b, negs=step_ids(cfg, b, fault)[2]) for b in batches])
    return losses, first, end


# ---- adapter ----

def to_program(tables: dict) -> dict:
    return {t: {"Embedding_0": {"embeddings": tables[t]}} for t in TABLES}


def from_program(tree) -> dict:
    return {t: tree[t]["Embedding_0"]["embeddings"] for t in TABLES}


def init_state(cfg: dict, key, optimizer) -> tuple:
    """(start, state): both tables from ``key``, a jitted call each; the
    program's state but ``consts``. ``start`` holds the key and no
    table: every row's start can be made again from it."""
    rows = np.arange(cfg["graph"]["num_nodes"] + 1)  # max_id + 2 of them
    tree = to_program({t: init_rows(cfg["dim"], table_key(key, t), rows)
                       for t in TABLES})
    state = {"params": tree, "opt_state": optimizer.init(tree)}
    start = {"key": np.asarray(key)}
    # the row leaves are changes already
    zero = np.zeros(1, np.float32)
    for t, tail in (("target", WALK), ("context", WALK), ("context", NEGS)):
        start[t + tail] = start["mu_" + t + tail] = zero
    start[TWICE] = start[OUTSIDE] = start[SAMPLER] = zero
    _run.update(cfg=cfg, key=np.asarray(key), hops=[])
    return start, state


@jax.jit
def _take(table, ids):
    return table[ids]


def first_gradient(state) -> dict:
    """The first gradient as the optimizer got it, from the program's
    Adam state after one step (mu_1 = (1 - b1) * g_1), on the rows that
    step named. Gathered on the device."""
    mu = from_program(state["opt_state"][0].mu)
    walk, negs = named_ids(_run["hops"][-1:])
    scale = 1.0 / (1.0 - ADAM_B1)
    return {
        "target" + WALK: np.asarray(_take(mu["target"], walk)) * scale,
        "context" + WALK: np.asarray(_take(mu["context"], walk)) * scale,
        "context" + NEGS: np.asarray(_take(mu["context"], negs)) * scale,
    }


@functools.lru_cache(maxsize=8)
def _outside_fn(dim: int):
    def changed(table, mu, key, named):
        """Rows outside ``named`` (a [rows] mask) whose bits differ from
        their start or whose first moment is not nought; block by block,
        so that no second table is made."""
        rows = table.shape[0]

        def block(lo):
            at = lo + jnp.arange(BLOCK_ROWS)
            ids = jnp.minimum(at, rows - 1)
            was = init_rows(dim, key, ids)
            bits = jax.lax.bitcast_convert_type
            differs = (bits(table[ids], jnp.uint32)
                       != bits(was, jnp.uint32)).any(axis=1)
            differs |= (mu[ids] != 0).any(axis=1)
            return (differs & (at < rows) & ~named[ids]).sum()

        return jax.lax.map(block, jnp.arange(0, rows, BLOCK_ROWS)).sum()

    return jax.jit(changed)


@functools.partial(jax.jit, static_argnums=0)
def _change(dim: int, table, key, ids):
    return table[ids] - init_rows(dim, key, ids)


def compared_state(state) -> dict:
    """The compared leaves of the program's state after the captured
    steps: rows gathered, and the rest counted, on the device."""
    cfg, key = _run["cfg"], jnp.asarray(_run["key"], jnp.uint32)
    tables = from_program(state["params"])
    mus = from_program(state["opt_state"][0].mu)
    walk, negs = named_ids(_run["hops"])
    dim = cfg["dim"]
    outside = _outside_fn(dim)
    out, changed = {}, 0
    for t, ids, tail in (("target", walk, WALK), ("context", walk, WALK),
                         ("context", negs, NEGS)):
        out[t + tail] = np.asarray(
            _change(dim, tables[t], table_key(key, t), ids))
        out["mu_" + t + tail] = np.asarray(_take(mus[t], ids))
    out[TWICE] = np.asarray(_take(mus["context"], twice_ids(_run["hops"])))
    rows = tables["target"].shape[0]
    for t, ids in (("target", walk), ("context", np.union1d(walk, negs))):
        named = np.zeros(rows, bool)
        named[ids] = True
        changed += int(outside(tables[t], mus[t], table_key(key, t), named))
    out[OUTSIDE] = np.array([changed], np.float32)
    out[SAMPLER] = off_sampler(cfg, _run["hops"])
    log.info(
        "node2vec reference: %d rows outside the %d captured steps' %d walk "
        "and %d negative ids changed; %d ids were a step's negative more "
        "than once", changed, len(_run["hops"]), len(walk), len(negs),
        len(out[TWICE]))
    return out


def drawn_fanouts(cfg: dict) -> list:
    """The walk: ``walk_len`` chained hops of one draw each."""
    return [1] * int(cfg["walk_len"])


@functools.lru_cache(maxsize=8)
def _inputs_fn(module):
    def inputs(batch, consts):
        src, pos, negs = module.apply(
            {"params": {}}, batch, consts, method=module._inputs)
        return src["ids"], pos["ids"], negs["ids"]

    return jax.jit(inputs)


def drawn_hops(model, state, batch) -> list:
    """The walk's columns, root first, then the negatives. A host-sampled
    batch carries pairs and negatives; a device-sampled one (roots +
    seed) is expanded by the module's own ``_inputs``, as inside its
    step, jitted alone. The walk is read out of ``src``: position ``j``
    of a path is the target of the first pair that names it."""
    if "src" in batch:
        src, negs = batch["src"]["ids"], batch["negs"]["ids"]
    else:
        src, _, negs = _inputs_fn(model.module)(batch, state["consts"])
    cfg = _run["cfg"]
    path_len = int(cfg["walk_len"]) + 1
    tgt, _ = pair_positions(path_len, *windows(cfg))
    first = [int(np.flatnonzero(tgt == j)[0]) for j in range(path_len)]
    src = np.asarray(jax.device_get(src), np.int64).reshape(-1, len(tgt))
    paths = src[:, first]
    negs = np.asarray(jax.device_get(negs), np.int64).reshape(-1)
    _run["hops"].append({"paths": paths, "negs": negs})
    return [paths[:, j] for j in range(path_len)] + [negs]


def reference_batch(spec, hops: list) -> dict:
    """The walks [B, walk_len + 1] and the negatives of one step: ids
    alone, the family reads no feature and no label."""
    cols = [np.asarray(h, np.int64).reshape(-1) for h in hops]
    return {"paths": np.stack(cols[:-1], axis=1), "negs": cols[-1]}


def batch_rows(cfg: dict, batch: dict, rows: int) -> dict:
    """The batch of the first ``rows`` roots, with their negatives."""
    per_root = len(batch["negs"]) // len(batch["paths"])
    return {"paths": batch["paths"][:rows],
            "negs": batch["negs"][:rows * per_root]}
