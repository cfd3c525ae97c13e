"""Plain reference and adapter of one full-neighbourhood graph-attention
training step: upstream's attention aggregator over the whole
neighbourhood (alibaba/euler ``tf_euler/python/run_loop.py --model gcn
--aggregator attention``, ``sparse_aggregators.py:84-133``
SingleAttentionAggregator and AttentionAggregator, ``encoders.py:165-215``
GCNEncoder, ``models/gcn.py:26``). RAGGED, over the true edge list from
the graph function, head by head, float32 at ``highest`` matmul
precision. Imports nothing of the program.

The ragged expansion (``expand``, ``neighbours``), the exact judgement of
the program's expansion (``judge_hop``, ``reference_batch``,
``drawn_hops``), Adam written out and the state plumbing are
``gcn_ppi_reference.py``'s, loaded by path as the harness loads a
configuration's file: the two configurations expand the same graph, and
their expansions are judged by one judge.

One step on ``B`` roots, head ``k`` of ``K`` (head width ``D = dim / K``),
a hop's set rows ``X_s [n, F]``, the next hop's set rows ``X_a [m, F]``,
the TRUE edges ``(i, j)`` of the hop's list (an edge listed twice counted
twice):

* ``P_s = X_s W_k``, ``P_a = X_a W_k`` (one ``W_k [F, D]``, no bias);
  ``s_i = P_s[i] . u_k``, ``a_j = P_a[j] . v_k``;
* ``e_ij = leaky_relu(s_i + a_j)`` at the configuration's
  ``attention_leaky_slope``; ``alpha_ij`` the softmax of ``e_ij`` over
  the true edges of ``i`` alone (by segment over the ragged list: no
  slot, no mask; a node with no edge gets no neighbour term);
* ``h_i = act(P_s[i] + sum_j alpha_ij P_a[j])``, relu in layer 1, none in
  layer 2; a layer's output is the ``K`` heads side by side.

Layer 1 on the roots (from hop 1) and on hop 1's set (from hop 2), layer
2 on the roots, a linear classifier, sigmoid cross-entropy averaged over
every element, the gradient by ``jax.grad`` of this forward, Adam.

The matmuls (the projections and the classifier) take ``precision``; the
gates (a head's two dot products ``D`` wide, which feed a softmax), the
logits, the softmax and the weighted sums are float32 whatever it is, as
the configuration states.

So that the steps of a run share one compiled program, the node arrays
are filled up with zero rows and the edge lists with edges of a parent
past the last (``bucketed``): such an edge belongs to no node's softmax,
a zero row is the neighbour of none, and neither reaches the loss or a
gradient (the tests hold it exact).

``FAULTS`` are the family's planted faults: this reference with one rule
broken, put in the program's place as the control is
(``scalable_sage_reddit_faults.py --workload
gcn_attention_ppi_device_train`` does, on the chip). A sound
implementation never takes those branches.
"""

from __future__ import annotations

import functools
import importlib.util
import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

log = logging.getLogger("benchmark")

SCOPE = "edge_softmax"


def refuse_program_without_edge_softmax() -> None:
    """End the run at once, exit code 1 and no result line, where the
    program that is loaded beside this file names no ``edge_softmax``
    scope: a program from before the cell. It has ``--aggregator
    attention`` and would run, but by four heads of scatter-form segment
    softmax over 1.72M padded slots and a gather of as many rows out of
    each head's projected set: seconds a step (PERF.md section 5), where
    a traced run needs 250 steps inside the 360 s a run of the check may
    take, and its step gives the cell's two readers nothing to read.
    Raises ``SystemExit`` with its message, as
    ``node2vec_ppi_reference.py`` does: the harness closes what it had
    opened, ``run.py`` prints the message and leaves with code 1. The
    reference used alone (no program loaded) is held to nothing."""
    import importlib
    import sys

    if "euler_tpu" not in sys.modules:
        return
    trace = importlib.import_module("euler_tpu.trace")
    if SCOPE in getattr(trace, "STEP_SCOPES", ()):
        return
    raise SystemExit(
        "gcn attention reference: the program's trace.STEP_SCOPES lacks "
        f"{SCOPE}: a program from before the attention cell, whose "
        "aggregator runs four heads of segment softmax in scatter form "
        "over 1.72M padded slots (seconds a step, past the time a run of "
        "the check may take) and whose step gives the cell's per-layer "
        "metrics nothing to read; this cell cannot run on it; no result")


refuse_program_without_edge_softmax()


def _beside(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    spec = importlib.util.spec_from_file_location(
        "benchmark_reference_beside_" + name[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_gcn = _beside("gcn_ppi_reference.py")
ADAM_B1 = _gcn.ADAM_B1
EXPANSION, OVERFLOW, TWICE = _gcn.EXPANSION, _gcn.OVERFLOW, _gcn.TWICE
neighbours, expand, judge_hop = _gcn.neighbours, _gcn.expand, _gcn.judge_hop
adam_init, adam_update = _gcn.adam_init, _gcn.adam_update
# the adapter's side of the expansion: nothing is drawn, the program's
# own expansion is handed on and judged edge by edge, a batch is its roots
drawn_fanouts = _gcn.drawn_fanouts
drawn_hops = _gcn.drawn_hops
reference_batch = _gcn.reference_batch
batch_rows = _gcn.batch_rows

# one rule of the attention broken, by name (``step_arrays``, ``loss_fn``)
FAULTS = ("padding_in_softmax", "duplicate_edge_once", "self_left_out",
          "heads_averaged", "one_gate", "slope_0p2",
          "second_hop_not_aggregated")


# ---- the ragged arrays of a step ----

def _once(pos, child) -> tuple:
    """Every (parent, child) pair once (the fault ``duplicate_edge_once``),
    in the list's order."""
    key = pos.astype(np.int64) * (int(child.max(initial=0)) + 1) + child
    _, first = np.unique(key, return_index=True)
    keep = np.sort(first)
    return pos[keep], child[keep]


_arrays: dict = {}


def step_arrays(spec, roots, fault=None) -> dict:
    """What one step's forward reads, from the graph function: ``x0``
    [B, F], ``x1`` [|s1|, F], ``x2`` [|s2|, F] feature rows of the roots
    and of both hops' sets, each hop's true edges as (parent position,
    index into the next set) ``p0``/``c0`` and ``p1``/``c1``, and the
    roots' labels ``y``."""
    key = (id(spec), np.asarray(roots).tobytes())
    sound = fault != "duplicate_edge_once"
    if sound and key in _arrays:
        return _arrays[key]
    x = expand(spec, roots)
    edges = [x["e0"], x["e1"]]
    if not sound:
        edges = [_once(*e) for e in edges]
    arrays = {
        "x0": spec.features(x["roots"]), "x1": spec.features(x["s1"]),
        "x2": spec.features(x["s2"]), "y": spec.labels(x["roots"]),
    }
    for h, (pos, child) in enumerate(edges):
        arrays["p%d" % h] = pos.astype(np.int32)
        arrays["c%d" % h] = child.astype(np.int32)
    if sound:
        while len(_arrays) >= 4:
            _arrays.pop(next(iter(_arrays)))
        _arrays[key] = arrays
    return arrays


def _filled(n: int) -> int:
    """``n`` rounded up to a multiple of half the power of two at or
    below it: 14,287 -> 16,384, 364,081 -> 393,216, 400,035 ->
    524,288; the sizes of a run's steps differ by a few hundred."""
    step = max(1 << max(int(n).bit_length() - 2, 0), 8)
    return -(-int(n) // step) * step


def bucketed(arrays: dict) -> dict:
    """``arrays`` with both hops' sets filled up with zero rows and both
    edge lists with edges (parent PAST THE LAST, child 0): such an edge
    is in no node's softmax (``_head`` reduces over one segment more than
    there are parents and drops it), and a zero row is named by no true
    edge. Exact; the steps of a run share one compiled program."""
    out = dict(arrays)
    for k in ("x1", "x2"):
        fill = _filled(len(arrays[k])) - len(arrays[k])
        out[k] = np.pad(arrays[k], ((0, fill), (0, 0)))
    for h, parents in enumerate(("x0", "x1")):
        p, c = arrays["p%d" % h], arrays["c%d" % h]
        fill = _filled(len(p)) - len(p)
        out["p%d" % h] = np.pad(p, (0, fill),
                                constant_values=len(out[parents]))
        out["c%d" % h] = np.pad(c, (0, fill))
    return out


# ---- the mathematics ----

def param_shapes(cfg: dict) -> dict:
    f, dim, c = cfg["feature_dim"], cfg["dim"], cfg["num_classes"]
    if cfg["aggregator"] != "attention" or len(cfg["fanouts"]) != 2:
        raise ValueError("reference covers two attention layers")
    heads = int(cfg["num_heads"])
    d = dim // heads
    out = {"w_out": (dim, c), "b_out": (c,)}
    for layer, fan_in in ((0, f), (1, dim)):
        for k in range(heads):
            out["w%d_%d" % (layer, k)] = (fan_in, d)
            out["u%d_%d" % (layer, k)] = (d,)
            out["v%d_%d" % (layer, k)] = (d,)
    return out


def init_params(cfg: dict, key) -> dict:
    """The benchmark's weights from the seed: N(0, 1/fan_in) kernels and
    gates (a gate is a [D, 1] kernel), zero bias, float32. One traced
    function, jit it at the call site."""
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        if name.startswith("b_"):
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32
            ) * (1.0 / shape[0]) ** 0.5
    return out


def _head(p_self, p_all, pos, child, u, v, slope, fault, slots):
    """One head over one ragged edge list: [n, D]
    ``sum_j alpha_ij P_a[j]`` (the neighbour term alone). ``pos`` [E]
    parent positions (``n``: an edge of no parent, see ``bucketed``),
    ``child`` [E] rows of ``p_all``."""
    n = p_self.shape[0]
    s = jnp.dot(p_self, v if fault == "one_gate" else u, precision="highest")
    a = jnp.dot(p_all, v, precision="highest")
    s = jnp.concatenate([s, jnp.zeros(1, s.dtype)])
    logit = jax.nn.leaky_relu(s[pos] + a[child], slope)
    top = jax.ops.segment_max(logit, pos, num_segments=n + 1)
    pad_logit = pad = None
    if fault == "padding_in_softmax":
        # every parent has ``slots`` slots; the unused ones name a zero
        # row (a = 0) and enter the max and the denominator
        count = jax.ops.segment_sum(
            jnp.ones_like(logit), pos, num_segments=n + 1)
        pad = jnp.maximum(slots - count, 0.0)
        pad_logit = jax.nn.leaky_relu(s, slope)
        top = jnp.where(pad > 0, jnp.maximum(top, pad_logit), top)
    # a parent with no edge has a max of -inf
    top = jax.lax.stop_gradient(jnp.where(jnp.isfinite(top), top, 0.0))
    e = jnp.exp(logit - top[pos])
    denom = jax.ops.segment_sum(e, pos, num_segments=n + 1)
    if pad is not None:
        denom = denom + pad * jnp.exp(pad_logit - top)
    total = jax.ops.segment_sum(
        p_all[child] * e[:, None], pos, num_segments=n + 1)
    return (total / jnp.maximum(denom, 1e-30)[:, None])[:n]


def loss_fn(params, arrays, cfg_key, precision="highest", fault=None):
    """Mean loss of one step's ``step_arrays``. ``cfg_key``: (heads,
    slope, the graph's widest row)."""
    heads, slope, slots = cfg_key
    if fault == "slope_0p2":
        slope = 0.2

    def layer(tail, x_self, x_all, pos, child, act, aggregated=True):
        outs = []
        for k in range(heads):
            w = params["w%s_%d" % (tail, k)]
            p_self = jnp.dot(x_self, w, precision=precision)
            out = 0.0 if fault == "self_left_out" else p_self
            if aggregated:
                p_all = jnp.dot(x_all, w, precision=precision)
                out = out + _head(
                    p_self, p_all, pos, child, params["u%s_%d" % (tail, k)],
                    params["v%s_%d" % (tail, k)], slope, fault, slots)
            outs.append(jax.nn.relu(out) if act else out)
        if fault == "heads_averaged":
            outs = [sum(outs) / heads] * heads
        return jnp.concatenate(outs, axis=1)

    p0, c0, p1, c1 = (arrays[k] for k in ("p0", "c0", "p1", "c1"))
    h0 = layer("0", arrays["x0"], arrays["x1"], p0, c0, True)
    h1 = layer("0", arrays["x1"], arrays["x2"], p1, c1, True,
               aggregated=fault != "second_hop_not_aggregated")
    z = layer("1", h0, h1, p0, c0, False)
    logits = jnp.dot(z, params["w_out"], precision=precision) \
        + params["b_out"]
    y = arrays["y"]
    # max(x, 0) - x*y + log(1 + exp(-|x|)), mean over every element
    per = (jnp.maximum(logits, 0) - logits * y
           + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    return per.mean()


@functools.lru_cache(maxsize=32)
def _value_and_grad(dtype, precision, fault, cfg_key):
    def step(p, arrays):
        def low(v):
            return v if v.dtype.kind == "i" else v.astype(dtype)

        def f(p_low):
            return loss_fn(p_low, {k: low(v) for k, v in arrays.items()},
                           cfg_key, precision, fault)

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
    the matmuls at the platform's default precision. ``dtype`` bfloat16
    is the control: parameters, features, projections, logits, softmax,
    loss and gradients in bfloat16; float32 master weights and Adam."""
    assert fault is None or fault in FAULTS, fault
    params = {k: jnp.asarray(start[k]) for k in param_shapes(cfg)}
    cfg_key = (int(cfg["num_heads"]), float(cfg["attention_leaky_slope"]),
               int(cfg["graph"]["max_degree"]))
    step = _value_and_grad(jnp.dtype(dtype), precision, fault, cfg_key)
    opt = adam_init(params)
    losses, first, off = [], None, 0
    clock = {"arrays": 0.0, "steps": 0.0}
    for batch in batches:
        t0 = time.time()
        arrays = step_arrays(batch["spec"], batch["roots"], fault)
        off += batch["off"]
        t1 = time.time()
        loss, g = step(params, bucketed(arrays))
        if first is None:
            first = g
        params, opt = adam_update(params, g, opt, cfg["learning_rate"])
        losses.append(float(loss))
        clock["arrays"] += t1 - t0
        clock["steps"] += time.time() - t1
    log.info(
        "gcn attention reference: %d steps followed (%s, precision %s%s): "
        "the ragged arrays from the graph function %.1f s, the steps %.1f s",
        len(batches), jnp.dtype(dtype).name, precision,
        ", fault " + fault if fault else "", clock["arrays"], clock["steps"])
    end = dict(params)
    # what the exact judgement of the program's expansions found off
    # (``reference_batch``). A fault planted in this file adds nothing of
    # its own to it: what the numbers do not see of a fault is not seen
    end[EXPANSION] = np.array([off], np.float32)
    end[OVERFLOW] = np.zeros(1, np.float32)
    return losses, first, end


# ---- adapter: the reference's names <-> the program's parameter tree ----

def _program_paths(names) -> dict:
    """A head's three leaves are its own sub-module's bias-free Dense
    kernels: the projection, the self gate, the neighbour gate."""
    out = {"w_out": ("predict", "kernel"), "b_out": ("predict", "bias")}
    dense = {"w": "Dense_0", "u": "Dense_1", "v": "Dense_2"}
    for name in names:
        if name in out:
            continue
        layer, k = name[1:].split("_")
        out[name] = ("encoder", "AttentionAggregator_" + layer,
                     "SingleAttentionAggregator_" + k, dense[name[0]],
                     "Dense_0", "kernel")
    return out


def to_program(params: dict) -> dict:
    tree: dict = {}
    for name, path in _program_paths(params).items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        leaf = params[name]
        # a gate is a [D, 1] kernel in the program
        node[path[-1]] = leaf[:, None] if name[0] in "uv" else leaf
    return tree


def from_program(tree, names) -> dict:
    out = {}
    for name, path in _program_paths(names).items():
        node = tree
        for k in path:
            node = node[k]
        out[name] = node[:, 0] if name[0] in "uv" else node
    return out


# the names of the parameters of the train() call in progress
_run = {"names": ()}


def init_state(cfg: dict, key, optimizer) -> tuple:
    """(start, state): the benchmark's weights from ``key`` in one jitted
    call, under the reference's names, and what the program's step takes
    (``params`` and the optimizer's state over them; the harness adds
    ``consts``)."""
    start = jax.jit(lambda k: init_params(cfg, k))(key)
    _run.update(names=tuple(start))
    tree = to_program(start)
    start = dict(start)
    start[EXPANSION] = start[OVERFLOW] = np.zeros(1, np.float32)
    _gcn._run.update(overflow=[])
    return start, {"params": tree, "opt_state": optimizer.init(tree)}


def first_gradient(state) -> dict:
    """The first gradient as the optimizer got it, from the program's
    Adam state after one step: mu_1 = (1 - b1) * g_1."""
    mu = jax.device_get(state["opt_state"])[0].mu
    return {
        k: np.asarray(v) / (1.0 - ADAM_B1)
        for k, v in from_program(mu, _run["names"]).items()
    }


def compared_state(state) -> dict:
    """The leaves whose change after the captured steps is compared: the
    parameters, the program's own count of nodes past a cap over the
    captured steps (``drawn_hops`` keeps it), and (nought on the
    program's side: see ``reference_batch``) the exact judgement's
    leaf."""
    out = {
        k: np.asarray(v) for k, v in from_program(
            jax.device_get(state["params"]), _run["names"]).items()
    }
    out[OVERFLOW] = np.array([sum(_gcn._run["overflow"])], np.float32)
    out[EXPANSION] = np.zeros(1, np.float32)
    return out
