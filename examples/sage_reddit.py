"""Supervised GraphSAGE on (synthetic) Reddit.

Reference equivalent: examples/sage_reddit.py:80-97 — batch 1000, fanouts
[4,4], dim 64, Adam 0.03, 2000 steps, softmax classes. Synthetic data at
Reddit scale (232965 nodes, 602-dim features, 41 classes) — see
examples/sage.py for why.

    PYTHONPATH=. python examples/sage_reddit.py [--steps 2000]
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import euler_tpu
from euler_tpu import train as train_lib
from euler_tpu.datasets import REDDIT, build_reddit
from euler_tpu.models import SupervisedGraphSage


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data_dir", default="/tmp/euler_tpu_reddit")
    ap.add_argument("--steps", type=int, default=2000)
    ap.add_argument("--batch_size", type=int, default=1000)
    args = ap.parse_args()

    build_reddit(args.data_dir)
    graph = euler_tpu.Graph(directory=args.data_dir)
    model = SupervisedGraphSage(
        label_idx=0,
        label_dim=REDDIT["label_dim"],
        metapath=[[0], [0]],
        fanouts=[4, 4],
        dim=64,
        feature_idx=1,
        feature_dim=REDDIT["feature_dim"],
        max_id=REDDIT["num_nodes"] - 1,
        sigmoid_loss=False,
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
