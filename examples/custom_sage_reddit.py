"""Build-your-own-GraphSAGE on (synthetic) Reddit from the primitive ops.

Reference equivalent: examples/gcn_sage_reddit.py — that example's point
is not the model (it re-implements mean-aggregator GraphSAGE) but the
EXTENSION API: a user model composed from the framework's primitives
(custom aggregator layer -> custom encoder -> custom model) rather than
the model zoo. The same recipe here, the euler_tpu way: the model is a
(host sample phase, flax module) pair —

  sample(graph, roots): ops.sample_fanout + graph.get_dense_feature
                        (numpy, runs in prefetch threads)
  _CustomSage(nn.Module): per-layer mean aggregation + softmax loss
                        (pure JAX, one XLA program)

For graphs with SPARSE id features instead of dense vectors, swap the
encoder for euler_tpu.nn.SparseSageEncoder (reference
encoders.py:522-560): host-side, gather per-hop padded sparse ids with
graph.get_sparse_feature; device-side the encoder embeds each slot
(16-dim, concatenated) and Sage-aggregates — same fanout/hop layout as
here.

    PYTHONPATH=. python examples/custom_sage_reddit.py [--steps 2000]
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import flax.linen as nn
import jax.numpy as jnp
import optax

import euler_tpu
from euler_tpu import ops
from euler_tpu import train as train_lib
from euler_tpu.datasets import REDDIT, build_reddit
from euler_tpu.models import base
from euler_tpu.nn import metrics


class MeanAggregator(nn.Module):
    """Neighbors-only mean aggregation (reference gcn_sage_reddit.py
    MeanAggregator: reduce_mean over the fanout axis, then dense)."""

    dim: int
    use_activation: bool = True

    @nn.compact
    def __call__(self, neigh):  # [batch, fanout, dim_in]
        agg = jnp.mean(neigh, axis=1)
        out = nn.Dense(self.dim, use_bias=False)(agg)
        return nn.relu(out) if self.use_activation else out


class _CustomSage(nn.Module):
    """The reference example's SageEncoder + softmax decoder: layer L
    aggregates hop h+1 into hop h for every remaining hop, no self/concat
    path (unlike the zoo's SageEncoder)."""

    fanouts: tuple
    dim: int
    num_classes: int

    @nn.compact
    def __call__(self, batch):
        hidden = batch["hops"]  # per-hop [n_h, feature_dim] features
        num_layers = len(self.fanouts)
        for layer in range(num_layers):
            agg = MeanAggregator(
                self.dim, use_activation=layer < num_layers - 1
            )
            hidden = [
                agg(
                    hidden[hop + 1].reshape(
                        hidden[hop].shape[0], self.fanouts[hop], -1
                    )
                )
                for hop in range(num_layers - layer)
            ]
        embedding = hidden[0]
        logits = nn.Dense(self.num_classes)(embedding)
        labels = batch["labels"]
        loss = optax.softmax_cross_entropy(logits, labels).mean()
        preds = nn.one_hot(jnp.argmax(logits, -1), self.num_classes)
        return base.ModelOutput(
            embedding=embedding,
            loss=loss,
            metric_name="f1",
            metric=metrics.f1_counts(labels, preds),
        )


class CustomSage(base.Model):
    metric_name = "f1"

    def __init__(self, fanouts, dim, feature_idx, feature_dim, label_idx,
                 label_dim, edge_type=(0,)):
        super().__init__()
        self.fanouts = tuple(fanouts)
        self.feature_idx = feature_idx
        self.feature_dim = feature_dim
        self.label_idx = label_idx
        self.label_dim = label_dim
        self.edge_types = [list(edge_type)] * len(fanouts)
        self.module = _CustomSage(self.fanouts, dim, label_dim)

    def sample(self, graph, inputs) -> dict:
        roots = np.asarray(inputs, dtype=np.int64).reshape(-1)
        ids_per_hop, _, _ = ops.sample_fanout(
            graph, roots, self.edge_types, list(self.fanouts)
        )
        hops = [
            graph.get_dense_feature(
                ids, [self.feature_idx], [self.feature_dim]
            )
            for ids in ids_per_hop
        ]
        labels = graph.get_dense_feature(
            roots, [self.label_idx], [self.label_dim]
        )
        return {"hops": hops, "labels": labels}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data_dir", default="/tmp/euler_tpu_reddit")
    ap.add_argument("--steps", type=int, default=2000)
    ap.add_argument("--batch_size", type=int, default=1000)
    args = ap.parse_args()

    build_reddit(args.data_dir)
    graph = euler_tpu.Graph(directory=args.data_dir)
    model = CustomSage(
        fanouts=[4, 4],
        dim=64,
        feature_idx=1,
        feature_dim=REDDIT["feature_dim"],
        label_idx=0,
        label_dim=REDDIT["label_dim"],
    )

    def source(step):
        return np.asarray(graph.sample_node(args.batch_size, -1))

    state, history = train_lib.train(
        model,
        graph,
        source,
        num_steps=args.steps,
        optimizer="adam",
        learning_rate=0.03,
        log_every=100,
        prefetch_threads=4,
        prefetch_depth=3,
    )
    print("final:", history[-1])


if __name__ == "__main__":
    main()
