"""Plain reference and adapter of the historical-store training step
(alibaba/euler ``tf_euler/python/models/graphsage.py`` ScalableSage and
``encoders.py`` ScalableSageEncoder; ``--model scalable_sage``), at toy
size: the second model family on the harness's seam, brought as files
only (this one, ``toy_store_costs.py``, ``toy_store.json`` and an entry
in ``BENCHMARK_toy.json``).

The model: two mean-aggregator layers with concat and a linear
classifier, as supervised GraphSAGE, but one drawn hop. Layer 1 reads its
neighbours' layer-0 embeddings from a per-node store of past steps
instead of drawing a second hop. One step, written from the description
of the upstream algorithm (its three session hooks and second optimizer),
in float32 at ``highest`` matmul precision, on roots ``r`` and their drawn
neighbours ``n``:

1. read the stale gradients ``stale = grad_store[r]`` and clear those rows;
2. loss and its gradients by the parameters and by the store rows read
   (``store[n]``); Adam (``learning_rate``) moves the parameters;
3. store loss ``sum(h0(r) * stale)``, ``h0`` the roots' layer-0
   embeddings: its gradient by the parameters as they were before step 2
   goes to a second Adam (``store_learning_rate``), which moves the
   parameters again;
4. scatter-add the gradients by the store rows read (of the loss and of
   the store loss) into ``grad_store[n]``;
5. write the fresh ``h0(r)`` into ``store[r]``.

Where a root is drawn twice into one batch, step 5 writes the row twice;
the reference keeps the later one (as numpy's indexed assignment does).

The adapter's side (the protocol is stated in
``benchmark/sage_reference.py``): the program's step takes ``params``,
``opt_state``, ``stores``, ``grad_stores`` and ``store_opt_state``; what
is compared after step 1 is the loss's gradient as the first Adam got
it, and after step 3 the change of the parameters, of the store, of the
gradient store and of the second Adam's first moment (named
``store_mu/<leaf>``: the store loss's gradients, which at two layers
reach layer 0 alone). Imports nothing of the program.
"""

from __future__ import annotations

import functools

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

STORE_MU = "store_mu/"


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


def train_steps(cfg: dict, start: dict, batches: list, dtype=jnp.float32,
                precision="highest"):
    """Follow ``len(batches)`` steps from ``start``. Returns (losses, the
    loss's first gradient by the parameters, the leaves of ``start``
    after the last step). ``dtype`` bfloat16 is the control: parameters,
    features, store rows, activations and gradients in bfloat16, master
    weights, stores and both Adams in float32."""
    names = [k for k in start if not k.startswith(STORE_MU)
             and k not in ("store0", "grad_store0")]

    def grads(p, x0, x1, reads, y, stale):
        p, x0, x1, reads, y, stale = jax.tree_util.tree_map(
            lambda a: a.astype(dtype), (p, x0, x1, reads, y, stale))

        def loss_fn(p, reads):
            loss, h0 = forward(p, x0, x1, reads, y, cfg, precision)
            return loss, h0

        def store_loss_fn(p, reads):
            _, h0 = forward(p, x0, x1, reads, y, cfg, precision)
            return jnp.sum(h0 * stale)

        (loss, h0), (gp, gr) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(p, reads)
        gp_s, gr_s = jax.grad(store_loss_fn, argnums=(0, 1))(p, reads)
        f32 = functools.partial(jax.tree_util.tree_map,
                                lambda a: a.astype(jnp.float32))
        return f32(loss), f32(h0), f32(gp), f32(gr), f32(gp_s), f32(gr_s)

    step = jax.jit(grads)
    params = {k: jnp.asarray(start[k]) for k in names}
    store = np.array(start["store0"], dtype=np.float32)
    grad_store = np.array(start["grad_store0"], dtype=np.float32)
    opt, store_opt = adam_init(params), adam_init(params)
    store_opt["m"] = {k: jnp.asarray(start[STORE_MU + k]) for k in names}
    losses, first = [], None
    for batch in batches:
        r, n = batch["roots"], batch["neighbours"]
        stale = grad_store[r]                                      # 1
        grad_store[r] = 0.0
        loss, h0, gp, gr, gp_s, gr_s = step(
            params, batch["x0"], batch["x1"], store[n], batch["y"], stale)
        if first is None:
            first = gp
        moved, opt = adam_update(params, gp, opt, cfg["learning_rate"])  # 2
        delta, store_opt = adam_update(                            # 3
            params, gp_s, store_opt, cfg["store_learning_rate"])
        params = {k: moved[k] + (delta[k] - params[k]) for k in names}
        np.add.at(grad_store, n, np.asarray(gr) + np.asarray(gr_s))  # 4
        store[r] = np.asarray(h0)                                  # 5
        losses.append(float(loss))
    end = dict(params)
    end["store0"], end["grad_store0"] = store, grad_store
    end.update({STORE_MU + k: store_opt["m"][k] for k in names})
    return losses, first, end


# ---- adapter ----

def init_state(cfg: dict, key, optimizer) -> tuple:
    """(start, state): weights, the store's uniform start and the zero
    gradient store from ``key``; the program's state but ``consts``."""
    rows = cfg["graph"]["num_nodes"] + 1   # the program's max_id + 2

    def make(k):
        p = init_params(cfg, k)
        p["store0"] = jax.random.uniform(
            jax.random.fold_in(k, 1000), (rows, cfg["dim"]), jnp.float32,
            0.0, cfg["store_init_maxval"])
        return p

    start = jax.jit(make)(key)
    store0 = start.pop("store0")
    tree = to_program(start)
    state = {
        "params": tree,
        "opt_state": optimizer.init(tree),
        "stores": [store0],
        "grad_stores": [jnp.zeros_like(store0)],
        "store_opt_state": optax.adam(cfg["store_learning_rate"]).init(tree),
    }
    start.update({STORE_MU + k: jnp.zeros_like(v) for k, v in start.items()})
    start["store0"], start["grad_store0"] = store0, jnp.zeros_like(store0)
    return start, state


def compared_state(state) -> dict:
    got = jax.device_get({k: state[k] for k in (
        "params", "stores", "grad_stores", "store_opt_state")})
    out = {k: np.asarray(v) for k, v in from_program(got["params"]).items()}
    out.update({
        STORE_MU + k: np.asarray(v)
        for k, v in from_program(got["store_opt_state"][0].mu).items()})
    out["store0"] = np.asarray(got["stores"][0])
    out["grad_store0"] = np.asarray(got["grad_stores"][0])
    return out


def drawn_fanouts(cfg: dict) -> list:
    """``fanouts`` counts layers here: one hop is drawn, at its first."""
    return [cfg["fanouts"][0]]


@functools.lru_cache(maxsize=8)
def _expand_fn(model):
    return jax.jit(model._expand_batch)


def drawn_hops(model, state, batch) -> list:
    """[roots, neighbours] of this step. A host-sampled batch carries
    them; a device-sampled one (roots + seed) is expanded by the model's
    own entry, as inside its step, jitted alone."""
    if "node_ids" not in batch:
        batch = _expand_fn(model)(batch, state["consts"])
    return [batch["node_ids"], batch["neigh_ids"]]


def reference_batch(spec, hops: list) -> dict:
    roots, neigh = (np.asarray(h, dtype=np.int64).reshape(-1) for h in hops)
    return {"roots": roots, "neighbours": neigh,
            "x0": spec.features(roots), "x1": spec.features(neigh),
            "y": spec.labels(roots)}


def batch_rows(cfg: dict, batch: dict, rows: int) -> dict:
    fan = cfg["fanouts"][0]
    return {k: v[:rows * (fan if k in ("neighbours", "x1") else 1)]
            for k, v in batch.items()}
