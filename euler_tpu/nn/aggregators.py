"""Dense (fanout-shaped) aggregators for sampled-neighbor encoders.

Reference equivalent: tf_euler/python/aggregators.py:25-113. Inputs are
(self_embedding [n, d], neigh_embedding [n, fanout, d]); everything is a
reduce + matmul, which XLA fuses and maps onto the MXU.
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from euler_tpu.nn.layers import Dense


class GCNAggregator(nn.Module):
    dim: int
    activation: Optional[Callable] = nn.relu

    @nn.compact
    def __call__(self, inputs):
        self_emb, neigh_emb = inputs
        with jax.named_scope("aggregate"):
            all_emb = jnp.concatenate(
                [self_emb[:, None, :], neigh_emb], axis=1)
            agg = all_emb.mean(axis=1)
        return Dense(self.dim, self.activation, use_bias=False)(agg)


class _BaseAggregator(nn.Module):
    dim: int
    activation: Optional[Callable] = nn.relu
    concat: bool = False

    def aggregate(self, neigh_emb):
        raise NotImplementedError

    @nn.compact
    def __call__(self, inputs):
        self_emb, neigh_emb = inputs
        dim = self.dim
        if self.concat:
            if dim % 2:
                raise ValueError("dim must be even when concat=True")
            dim //= 2
        # the Dense layers inside carry their own scope, "dense"
        with jax.named_scope("aggregate"):
            agg = self.aggregate(neigh_emb)
            from_self = Dense(dim, self.activation, use_bias=False)(self_emb)
            from_neigh = Dense(dim, self.activation, use_bias=False)(agg)
            if self.concat:
                return jnp.concatenate([from_self, from_neigh], axis=1)
            return from_self + from_neigh


class MeanAggregator(_BaseAggregator):
    def aggregate(self, neigh_emb):
        return neigh_emb.mean(axis=1)


class MeanPoolAggregator(_BaseAggregator):
    def aggregate(self, neigh_emb):
        h = Dense(self.dim, nn.relu)(neigh_emb)
        return h.mean(axis=1)


class MaxPoolAggregator(_BaseAggregator):
    def aggregate(self, neigh_emb):
        h = Dense(self.dim, nn.relu)(neigh_emb)
        return h.max(axis=1)


AGGREGATORS = {
    "gcn": GCNAggregator,
    "mean": MeanAggregator,
    "meanpool": MeanPoolAggregator,
    "maxpool": MaxPoolAggregator,
}


def get(name: str):
    return AGGREGATORS.get(name)
