"""Core layers (flax.linen).

Reference equivalent: tf_euler/python/base_layers.py (Dense :69,
Embedding :116, SparseEmbedding :146). SparseEmbedding here consumes the
padded (ids, mask) pairs produced by ops.get_sparse_feature instead of a
tf.SparseTensor — a masked lookup-and-combine that stays fixed-shape on TPU.
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


class Dense(nn.Module):
    dim: int
    activation: Optional[Callable] = None
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        with jax.named_scope("dense"):
            y = nn.Dense(self.dim, use_bias=self.use_bias)(x)
            if self.activation is not None:
                y = self.activation(y)
        return y


class Embedding(nn.Module):
    """Id embedding table of size max_id+1 (ids are clipped into range;
    callers pass max_id+1 as the default/padding id like the reference)."""

    num: int
    dim: int
    stddev: float = 0.1

    @nn.compact
    def __call__(self, ids):
        table = self.param(
            "embeddings",
            nn.initializers.truncated_normal(stddev=self.stddev),
            (self.num, self.dim),
        )
        ids = jnp.clip(ids, 0, self.num - 1)
        return table[ids]


class SparseEmbedding(nn.Module):
    """Masked combine over padded sparse-id features.

    combiner 'sum' matches the reference default
    (base_layers.py:146 embedding_lookup_sparse combiner='sum').
    """

    num: int
    dim: int
    combiner: str = "sum"
    stddev: float = 0.0002

    @nn.compact
    def __call__(self, ids, mask):
        table = self.param(
            "embeddings",
            nn.initializers.truncated_normal(stddev=self.stddev),
            (self.num, self.dim),
        )
        ids = jnp.clip(ids, 0, self.num - 1)
        emb = table[ids] * mask[..., None]  # [n, L, dim]
        total = emb.sum(axis=-2)
        if self.combiner == "sum":
            return total
        if self.combiner == "mean":
            denom = jnp.maximum(mask.sum(axis=-1, keepdims=True), 1.0)
            return total / denom
        raise ValueError(f"unknown combiner {self.combiner}")
