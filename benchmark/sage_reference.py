"""Plain reference of one supervised GraphSAGE training step.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
mean aggregator over a two-hop sampled neighbourhood, concat of the self
and neighbour branches, a linear classifier, sigmoid or softmax
cross-entropy, gradients by ``jax.grad`` of this forward, and Adam written
out. Follows alibaba/euler ``tf_euler/python/models/graphsage.py``
(SupervisedGraphSage), ``encoders.py`` (SageEncoder) and
``aggregators.py`` (MeanAggregator). Imports nothing of the program.

Departure from the published description: none in the mathematics. The
reference is handed the ids the sampler drew (the draws themselves are
judged against the graph in ``check.py``) and reads features and labels
from the benchmark's own graph function.

This module is also the configuration's adapter, the one protocol the
harness and ``check.py`` know a model family by (a configuration's file
names its module under ``"reference"``; the next family brings its own):

* ``init_state(cfg, key, optimizer)``  (start, state): the reference-named
  leaves everything is followed from, and the whole of what the program's
  step takes but ``consts`` (the harness adds the tables it built);
* ``drawn_fanouts(cfg)``, ``drawn_hops(model, state, batch)``  the chained
  fan-outs of a step's draws and the ids each hop used;
* ``reference_batch(spec, hops)``, ``batch_rows(cfg, batch, rows)``  what
  the reference trains on, from the graph function, and its first-rows
  cut (the planted faults: half of the batch, one chip's share);
* ``first_gradient(state)``, ``compared_state(state)``  the named leaves
  compared after step 1 (the gradient as the optimizer got it) and after
  step 3 (against ``start``: the change), given the program's state;
* ``train_steps(cfg, start, batches, dtype, precision)``  the reference.

The work a step needs by shape (edges, FLOPs, bytes) is the cost function
the configuration names under ``"costs"`` (``configs/graphsage_costs.py``).

``precision=None`` gives the same float32 step at the platform's default
matmul precision: the arithmetic the configuration states (see
``check.py`` for why both are kept). ``dtype=jnp.bfloat16`` gives the
control: the same step with parameters, features, activations, loss and
gradients in bfloat16 (float32 master weights and Adam), the nearest
precision below the configuration's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def param_shapes(cfg: dict) -> dict:
    f, d, c = cfg["feature_dim"], cfg["dim"], cfg["num_classes"]
    if cfg["aggregator"] != "mean" or len(cfg["fanouts"]) != 2:
        raise ValueError("reference covers the two-hop mean aggregator")
    h = d // 2 if cfg["concat"] else d
    return {
        "w_self0": (f, h), "w_neigh0": (f, h),
        "w_self1": (d, h), "w_neigh1": (d, h),
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


def _branch(x, w, act, precision):
    y = jnp.dot(x, w, precision=precision)
    return jax.nn.relu(y) if act else y


def _aggregate(self_x, neigh_x, fanout, w_self, w_neigh, act, concat,
               precision):
    mean = neigh_x.reshape(self_x.shape[0], fanout, -1).mean(axis=1)
    a = _branch(self_x, w_self, act, precision)
    b = _branch(mean, w_neigh, act, precision)
    return jnp.concatenate([a, b], axis=1) if concat else a + b


def loss_fn(params, x0, x1, x2, labels, cfg, precision="highest"):
    """Mean loss of one batch. x0 [B, F] roots, x1 [B*f1, F], x2
    [B*f1*f2, F] features in hop order; labels [B, C]."""
    f1, f2 = cfg["fanouts"]
    c = cfg["concat"]
    h0 = _aggregate(x0, x1, f1, params["w_self0"], params["w_neigh0"],
                    True, c, precision)
    h1 = _aggregate(x1, x2, f2, params["w_self0"], params["w_neigh0"],
                    True, c, precision)
    z = _aggregate(h0, h1, f1, params["w_self1"], params["w_neigh1"],
                   False, c, precision)
    logits = jnp.dot(z, params["w_out"], precision=precision) + params["b_out"]
    if cfg["sigmoid_loss"]:
        # max(x, 0) - x*y + log(1 + exp(-|x|)), mean over every element
        per = (jnp.maximum(logits, 0) - logits * labels
               + jnp.log1p(jnp.exp(-jnp.abs(logits))))
        return per.mean()
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -(labels * logp).sum(axis=-1).mean()


def adam_init(params: dict) -> dict:
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    return {"m": zeros, "v": dict(zeros), "t": 0}


def adam_update(params, grads, opt, lr):
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


def train_steps(cfg: dict, params: dict, batches: list, dtype=jnp.float32,
                precision="highest"):
    """Follow ``len(batches)`` steps from ``params``. Each batch is
    (x0, x1, x2, labels) float32 arrays. Returns (losses, first gradient,
    parameters after the last step), all float32.

    dtype float32, precision "highest": the reference. precision None:
    the same float32 step with every matmul at the platform's default
    precision, which is what the configuration states (on a TPU: operands
    rounded to bfloat16, float32 accumulation; on a CPU: the same as
    highest). dtype bfloat16: the control."""

    def value_and_grad(p, batch):
        def f(p_low):
            x0, x1, x2, y = (a.astype(dtype) for a in batch)
            return loss_fn(p_low, x0, x1, x2, y, cfg, precision)

        p_low = {k: v.astype(dtype) for k, v in p.items()}
        loss, g = jax.value_and_grad(f)(p_low)
        return loss.astype(jnp.float32), {
            k: v.astype(jnp.float32) for k, v in g.items()
        }

    step = jax.jit(value_and_grad)
    opt = adam_init(params)
    losses, first = [], None
    for batch in batches:
        loss, g = step(params, batch)
        if first is None:
            first = g
        params, opt = adam_update(params, g, opt, cfg["learning_rate"])
        losses.append(float(loss))
    return losses, first, params


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
    """The leaves whose change after the captured steps is compared:
    the parameters, under the names of ``start``."""
    return {
        k: np.asarray(v)
        for k, v in from_program(jax.device_get(state["params"])).items()
    }


def drawn_fanouts(cfg: dict) -> list:
    """Hop h+1 holds ``drawn_fanouts[h]`` picks per row of hop h."""
    return list(cfg["fanouts"])


def reference_batch(spec, hops: list) -> tuple:
    """(x0, x1, x2, labels): features of every hop and the roots' labels,
    from the graph function (not from any table the program built)."""
    x = [spec.features(np.asarray(h).reshape(-1)) for h in hops]
    y = spec.labels(np.asarray(hops[0]).reshape(-1))
    return x[0], x[1], x[2], y


def batch_rows(cfg: dict, batch: tuple, rows: int) -> tuple:
    """The batch of the first ``rows`` roots, with their draws."""
    f1, f2 = cfg["fanouts"]
    x0, x1, x2, y = batch
    return (x0[:rows], x1[:rows * f1], x2[:rows * f1 * f2], y[:rows])


@functools.lru_cache(maxsize=8)
def _hops_fn(module):
    def hops(params, consts, batch):
        out = module.apply({"params": params}, batch, consts,
                           method=module._hops)
        return [h["gids"] for h in out]

    return jax.jit(hops)


def drawn_hops(model, state, batch) -> list:
    """The ids each hop of this step used, in hop order. A host-sampled
    batch carries them; a device-sampled batch (roots + seed) is expanded
    by the program's own draw entry (the module's ``_hops``: the same key
    derivation, routing and kernel as inside the step), jitted alone."""
    if "hops" in batch:
        return [h["gids"] for h in batch["hops"]]
    return _hops_fn(model.module)(state["params"], state["consts"], batch)
