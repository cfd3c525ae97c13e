"""GAT supervised model.

Reference equivalent: tf_euler/python/models/gat.py:25 + the AttEncoder
(encoders.py:563-632). Host: sample nb_num neighbors + gather features into
the [B, nb+1, F] sequence; device: all-pairs attention heads.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from euler_tpu.models import base
from euler_tpu.nn import metrics
from euler_tpu.nn.encoders import AttEncoder


class _GATModule(nn.Module):
    head_num: int
    hidden_dim: int
    num_classes: int
    sigmoid_loss: bool = True
    nb_num: int = 5
    adj_key: str = ""
    feature_dim: int = 0  # width of the rows cut from the stored table

    def setup(self):
        self.encoder = AttEncoder(
            head_num=self.head_num,
            hidden_dim=self.hidden_dim,
            out_dim=self.num_classes,
        )

    def _seq_ids(self, batch, consts):
        if "seq_ids" in batch:
            return batch["seq_ids"]
        # device sampling: draw the nb_num attention neighbors here
        import jax

        from euler_tpu.graph import device as device_graph

        roots = batch["roots"]
        key = jax.random.PRNGKey(batch["seed"][0])
        nbrs = device_graph.sample_neighbor(
            consts["adj"][self.adj_key], roots, key, self.nb_num
        )
        return jnp.concatenate([roots[:, None], nbrs], axis=1)

    def _logits(self, batch, consts, seq_ids):
        if "seq" in batch:
            return self.encoder(batch["seq"])
        # device-resident features: gather [B, nb+1, fdim] from the table
        return self.encoder(
            base.gather_rows(consts["features"], seq_ids, self.feature_dim)
        )

    def embed(self, batch, consts=None):
        seq_ids = None if "seq" in batch else self._seq_ids(batch, consts)
        return self._logits(batch, consts, seq_ids)

    def __call__(self, batch, consts=None):
        # The reference AttEncoder's out_dim IS num_classes (logits).
        seq_ids = None if "seq" in batch else self._seq_ids(batch, consts)
        logits = self._logits(batch, consts, seq_ids)
        labels = base.lookup_labels(
            batch, consts,
            seq_ids[:, 0] if seq_ids is not None else None,
        )
        loss, predictions = base.supervised_decoder(
            logits, labels, self.sigmoid_loss
        )
        return base.ModelOutput(
            embedding=logits,
            loss=loss,
            metric_name="f1",
            metric=metrics.f1_counts(labels, predictions),
        )


class GAT(base.Model):
    metric_name = "f1"

    def __init__(
        self,
        label_idx: int,
        label_dim: int,
        feature_idx: int,
        feature_dim: int,
        max_id: int = -1,
        head_num: int = 1,
        hidden_dim: int = 128,
        nb_num: int = 5,
        edge_type: int = 0,
        num_classes: Optional[int] = None,
        sigmoid_loss: bool = True,
        device_features: bool = False,
        feature_dtype: Optional[str] = None,
        device_sampling: bool = False,
        train_node_type: int = -1,
    ):
        super().__init__()
        self.feature_dtype = feature_dtype
        self.device_features = base.resolve_device_features(
            device_features, feature_idx, max_id
        )
        self.max_id = max_id
        self.init_device_sampling(device_sampling)
        self.train_node_type = train_node_type
        self.label_idx = label_idx
        self.label_dim = label_dim
        self.feature_idx = feature_idx
        self.feature_dim = feature_dim
        self.nb_num = nb_num
        self.edge_type = [edge_type] if np.isscalar(edge_type) else list(
            edge_type
        )
        self._adj_key = self.adj_key(self.edge_type)
        self.module = _GATModule(
            head_num=head_num,
            hidden_dim=hidden_dim,
            num_classes=num_classes or label_dim,
            sigmoid_loss=sigmoid_loss,
            nb_num=nb_num,
            adj_key=self._adj_key,
            feature_dim=feature_dim,
        )

    def build_consts(self, graph) -> dict:
        consts = super().build_consts(graph)
        if self.device_sampling:
            self.add_sampling_consts(consts, graph, [self.edge_type])
        return consts

    def sample(self, graph, inputs) -> dict:
        roots = np.asarray(inputs, dtype=np.int64).reshape(-1)
        if self.device_sampling:
            return self.device_sample_batch(roots)
        B = len(roots)
        default = self.max_id + 1 if self.max_id >= 0 else -1
        nbrs, _, _ = graph.sample_neighbor(
            roots, self.edge_type, self.nb_num, default
        )
        if self.device_features:
            seq_ids = np.concatenate(
                [roots.reshape(B, 1), nbrs.reshape(B, self.nb_num)], axis=1
            )
            seq_ids = np.clip(seq_ids, 0, self.max_id + 1).astype(np.int32)
            return {"seq_ids": seq_ids}
        node_feats = graph.get_dense_feature(
            roots, [self.feature_idx], [self.feature_dim]
        ).reshape(B, 1, self.feature_dim)
        nbr_feats = graph.get_dense_feature(
            nbrs.reshape(-1), [self.feature_idx], [self.feature_dim]
        ).reshape(B, self.nb_num, self.feature_dim)
        seq = np.concatenate([node_feats, nbr_feats], axis=1)
        labels = graph.get_dense_feature(
            roots, [self.label_idx], [self.label_dim]
        )
        return {"seq": seq, "labels": labels}
