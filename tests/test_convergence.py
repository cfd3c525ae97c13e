"""Convergence gates on the planted-community graph.

Trend-only loss checks can't catch a model that compiles, descends, and
still fails to learn what a GNN should learn. These tests train each
supervised model family to convergence on a graph whose labels are a
known function of neighborhood structure (euler_tpu.datasets.build_planted)
and gate the micro-F1 against targets COMPUTED from the generator arrays:

  feat_acc  — nearest-centroid accuracy on raw node features (what a
              featureless-of-graph classifier can reach, ~0.56)
  hop1_acc  — the same after averaging each node's 1-hop neighborhood
              (~0.94): the separability a single aggregation layer exposes

A converged 2-hop GNN must clearly beat feat_acc and approach hop1_acc.
Reference bar being mirrored: supervised GraphSAGE recovers PPI
micro-F1 0.6-0.8 / Reddit 0.93-0.95 (BASELINE.md) — unavailable offline,
so the planted graph provides the known-achievable target instead.

Also bounds the ScalableGCN historical-embedding staleness: its converged
F1 must match plain GCN's within a small tolerance (VERDICT round 1
weak #6 — quantify the stale-store approximation).
"""

import numpy as np
import pytest

MARGIN = 0.08  # slack below hop1_acc: finite training + eval sampling noise


@pytest.fixture(scope="module")
def planted(tmp_path_factory):
    from euler_tpu.datasets import build_planted, nearest_centroid_accuracy

    d = tmp_path_factory.mktemp("planted")
    out_dir, info = build_planted(str(d))
    info["out_dir"] = out_dir
    feat_acc = nearest_centroid_accuracy(info, use_neighbors=False)
    hop1_acc = nearest_centroid_accuracy(info, use_neighbors=True)
    # generator sanity: aggregation must be the thing that makes the task
    # solvable, else the gates below prove nothing
    assert feat_acc < 0.7
    assert hop1_acc > 0.9
    import euler_tpu

    graph = euler_tpu.Graph(directory=out_dir)
    return graph, info, feat_acc, hop1_acc


NUM_NODES = 2000
NUM_CLASSES = 4
FEATURE_DIM = 16


def _train_and_eval(model, graph, num_steps=300, batch=128, lr=0.01,
                    seed=3):
    from euler_tpu import train as train_lib

    def source_fn(step):
        return graph.sample_node(batch, -1)

    state, _ = train_lib.train(
        model, graph, source_fn,
        num_steps=num_steps, learning_rate=lr, optimizer="adam",
        log_every=100, seed=seed,
    )
    # batch sizes must divide the conftest's 8-device mesh: 400 = 8 * 50
    ids = np.arange(NUM_NODES, dtype=np.int64)
    batches = [ids[i:i + 400] for i in range(0, NUM_NODES, 400)]
    result = train_lib.evaluate(model, graph, batches, state)
    return result["f1"]


def test_graphsage_learns_neighborhood_labels(planted):
    from euler_tpu.models import SupervisedGraphSage

    graph, info, feat_acc, hop1_acc = planted
    model = SupervisedGraphSage(
        label_idx=0, label_dim=NUM_CLASSES,
        metapath=[[0], [0]], fanouts=[10, 10], dim=32,
        feature_idx=1, feature_dim=FEATURE_DIM, max_id=NUM_NODES - 1,
        sigmoid_loss=False,
    )
    f1 = _train_and_eval(model, graph)
    assert f1 > feat_acc + 0.2, (
        f"GraphSAGE f1 {f1:.3f} is no better than single-node features "
        f"({feat_acc:.3f}): aggregation is not learning"
    )
    assert f1 > hop1_acc - MARGIN, (
        f"GraphSAGE f1 {f1:.3f} below the 1-hop separability bound "
        f"{hop1_acc:.3f} - {MARGIN}"
    )


def test_graphsage_device_sampling_learns(planted):
    """The HBM-resident sampling path must reach the same convergence
    gate as the host path — same distribution, same learning outcome."""
    from euler_tpu.models import SupervisedGraphSage

    graph, info, feat_acc, hop1_acc = planted
    model = SupervisedGraphSage(
        label_idx=0, label_dim=NUM_CLASSES,
        metapath=[[0], [0]], fanouts=[10, 10], dim=32,
        feature_idx=1, feature_dim=FEATURE_DIM, max_id=NUM_NODES - 1,
        sigmoid_loss=False, device_features=True, device_sampling=True,
    )
    f1 = _train_and_eval(model, graph)
    assert f1 > feat_acc + 0.2, (
        f"device-sampling f1 {f1:.3f} vs feature bound {feat_acc:.3f}"
    )
    assert f1 > hop1_acc - MARGIN, (
        f"device-sampling f1 {f1:.3f} below 1-hop bound "
        f"{hop1_acc:.3f} - {MARGIN}"
    )


def test_chunked_train_learns(planted):
    """train() runs a device-sampled model up to CHUNK_STEPS steps a
    dispatch through one traced program: that loop must converge as
    well, and it must really have run chunks (a hook that takes the
    keywords is called once a dispatch)."""
    from euler_tpu import train as train_lib
    from euler_tpu.models import SupervisedGraphSage

    graph, info, feat_acc, hop1_acc = planted
    model = SupervisedGraphSage(
        label_idx=0, label_dim=NUM_CLASSES,
        metapath=[[0], [0]], fanouts=[10, 10], dim=32,
        feature_idx=1, feature_dim=FEATURE_DIM, max_id=NUM_NODES - 1,
        sigmoid_loss=False, device_features=True, device_sampling=True,
    )
    ends = []
    state, _ = train_lib.train(
        model, graph, lambda step: graph.sample_node(128, -1), 300,
        learning_rate=0.01, optimizer="adam", log_every=300, seed=3,
        step_hook=lambda step, state=None, batch=None, loss=None:
            ends.append(step),
    )
    # the hook's three single first steps, then chunks of ten
    assert ends == [1, 2, 3, *range(13, 300, 10), 300], ends
    ids = np.arange(NUM_NODES, dtype=np.int64)
    batches = [ids[i:i + 400] for i in range(0, NUM_NODES, 400)]
    f1 = train_lib.evaluate(model, graph, batches, state)["f1"]
    assert f1 > hop1_acc - MARGIN, (
        f"chunked-train f1 {f1:.3f} below 1-hop bound {hop1_acc:.3f}"
    )


def test_gat_learns_neighborhood_labels(planted):
    from euler_tpu.models import GAT

    graph, info, feat_acc, hop1_acc = planted
    model = GAT(
        label_idx=0, label_dim=NUM_CLASSES,
        feature_idx=1, feature_dim=FEATURE_DIM, max_id=NUM_NODES - 1,
        head_num=2, hidden_dim=32, nb_num=10,
        sigmoid_loss=False,
    )
    f1 = _train_and_eval(model, graph)
    # GAT here is single-layer attention over the 1-hop neighborhood: gate
    # against clearly-beats-features; the hop1 bound is its ceiling
    assert f1 > feat_acc + 0.2, (
        f"GAT f1 {f1:.3f} vs single-node feature bound {feat_acc:.3f}"
    )


def test_gcn_and_scalable_gcn_converge_within_tolerance(planted):
    """Plain full-neighbor GCN and ScalableGCN (stale historical stores)
    must both learn the planted labels, and the stale-store approximation
    must cost at most 0.05 F1 at convergence."""
    from euler_tpu.models import ScalableGCN, SupervisedGCN

    graph, info, feat_acc, hop1_acc = planted
    gcn = SupervisedGCN(
        label_idx=0, label_dim=NUM_CLASSES,
        metapath=[[0], [0]], dim=32,
        # static pad caps sized for the eval batches (400 roots, full
        # 2-hop expansion of an avg-degree-10 graph)
        max_nodes_per_hop=[4096, 4096],
        max_edges_per_hop=[16384, 32768],
        feature_idx=1, feature_dim=FEATURE_DIM, max_id=NUM_NODES - 1,
        sigmoid_loss=False,
    )
    f1_gcn = _train_and_eval(gcn, graph, batch=96)
    assert f1_gcn > feat_acc + 0.2, (
        f"GCN f1 {f1_gcn:.3f} vs feature bound {feat_acc:.3f}"
    )

    scal = ScalableGCN(
        label_idx=0, label_dim=NUM_CLASSES,
        edge_type=[0], num_layers=2, dim=32,
        max_id=NUM_NODES - 1, max_neighbors=10,
        feature_idx=1, feature_dim=FEATURE_DIM,
        sigmoid_loss=False,
    )
    f1_scal = _train_and_eval(scal, graph, batch=96)
    assert f1_scal > feat_acc + 0.2, (
        f"ScalableGCN f1 {f1_scal:.3f} vs feature bound {feat_acc:.3f}"
    )
    assert f1_scal > f1_gcn - 0.05, (
        f"stale-store ScalableGCN f1 {f1_scal:.3f} degrades more than "
        f"0.05 below plain GCN {f1_gcn:.3f}"
    )

    # the device full-neighborhood path (adjacency slab, no host dedup)
    # must converge equivalently
    scal_dev = ScalableGCN(
        label_idx=0, label_dim=NUM_CLASSES,
        edge_type=[0], num_layers=2, dim=32,
        max_id=NUM_NODES - 1, max_neighbors=10,
        feature_idx=1, feature_dim=FEATURE_DIM,
        sigmoid_loss=False, device_features=True, device_sampling=True,
    )
    f1_dev = _train_and_eval(scal_dev, graph, batch=96)
    assert f1_dev > f1_gcn - 0.05, (
        f"device-sampling ScalableGCN f1 {f1_dev:.3f} degrades more "
        f"than 0.05 below plain GCN {f1_gcn:.3f}"
    )


def _embedding_community_accuracy(emb, communities):
    """Nearest-centroid community recovery in embedding space: centroids
    fit from the TRUE communities on even nodes, accuracy on odd nodes.
    Random = 1/NUM_CLASSES = 0.25."""
    emb = emb / (np.linalg.norm(emb, axis=1, keepdims=True) + 1e-9)
    n = len(communities)
    train = np.arange(n) % 2 == 0
    centroids = np.stack(
        [
            emb[train & (communities == c)].mean(0)
            for c in range(NUM_CLASSES)
        ]
    )
    pred = (emb[~train] @ centroids.T).argmax(1)
    return float((pred == communities[~train]).mean())


_UNSUP_WORKER = """
import sys
import numpy as np
import euler_tpu
from euler_tpu import models
from euler_tpu import train as train_lib

family, out_dir, n_nodes = sys.argv[1], sys.argv[2], int(sys.argv[3])
graph = euler_tpu.Graph(directory=out_dir)
steps = 400 if family == "line2" else 300
if family == "line2":
    m = models.LINE(node_type=-1, edge_type=[0], max_id=n_nodes - 1,
                    dim=32, order=2, num_negs=5)
elif family.startswith("node2vec_biased"):
    m = models.Node2Vec(
        node_type=-1, edge_type=[0], max_id=n_nodes - 1, dim=32,
        walk_len=3, walk_p=0.5, walk_q=2.0, num_negs=5,
        device_sampling=family != "node2vec_biased",
    )
    if family.endswith("_alias"):
        # round-5 exact rejection-sampled walk over flat-CSR alias
        # tables — must learn the same structure as the slab walk
        m.set_sampling_options(alias=True)
else:
    m = models.GraphSage(
        node_type=-1, edge_type=[0], max_id=n_nodes - 1,
        metapath=[[0], [0]], fanouts=[5, 5], dim=32, num_negs=5,
        use_id=True, embedding_dim=32,
    )
state, hist = train_lib.train(
    m, graph, lambda s: graph.sample_node(128, -1),
    num_steps=steps, learning_rate=0.05, optimizer="adam", log_every=200,
)
emb = train_lib.save_embedding(m, graph, n_nodes - 1, state,
                               batch_size=400)
np.save(sys.argv[4], emb)
print("MRR", hist[-1]["mrr"], flush=True)
"""


@pytest.mark.parametrize(
    "family,acc_floor",
    [
        ("line2", 0.7),
        ("node2vec_biased", 0.9),
        ("node2vec_biased_device", 0.9),
        ("node2vec_biased_alias", 0.9),
        ("unsup_sage", 0.55),
    ],
)
def test_unsupervised_embeddings_recover_communities(planted, family,
                                                     acc_floor, tmp_path):
    """Unsupervised gates: loss/MRR trends can't catch an embedding that
    descends without learning structure. On the planted-community graph
    (intra_p=0.9) the community must be recoverable from the LEARNED
    embeddings alone (no input features — id embeddings trained purely
    from graph structure): nearest-centroid accuracy far above the 0.25
    random baseline. Floors are calibrated ~0.1 under single-seed
    observed values (LINE 0.89, biased Node2Vec 1.00, unsup GraphSage
    0.73). The device variant runs the same biased walk (d_tx
    reweighting) inside the jitted step.

    Each family trains in its OWN subprocess: back-to-back trainings in
    one process can starve an XLA-CPU collective rendezvous past its
    hard 40 s abort on this oversubscribed 8-virtual-device host."""
    import os
    import subprocess
    import sys

    graph, info, _, _ = planted
    comm = info["communities"]
    out_npy = str(tmp_path / "emb.npy")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(__file__))
    r = subprocess.run(
        [sys.executable, "-c", _UNSUP_WORKER, family, info["out_dir"],
         str(NUM_NODES), out_npy],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    mrr = float(r.stdout.split("MRR")[1].strip())
    assert mrr > 0.5, r.stdout
    emb = np.load(out_npy)
    acc = _embedding_community_accuracy(emb, comm)
    assert acc > acc_floor, (
        f"{family}: embedding community accuracy {acc:.3f} below "
        f"{acc_floor} (random = 0.25)"
    )
