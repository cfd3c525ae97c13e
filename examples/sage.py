"""Self-contained supervised GraphSAGE on (synthetic) PPI.

Reference equivalent: examples/sage.py:80-98 — batch 512, fanouts [10,10],
dim 256, Adam 0.01, 2000 steps, streaming micro-F1. Data prep is the
synthetic PPI-scale generator (euler_tpu/datasets.py) because this
environment has no network egress; swap in real PPI by pointing --data_dir
at a directory of converted .dat partitions (euler_tpu.graph.convert).

    PYTHONPATH=. python examples/sage.py [--steps 2000] [--data_dir DIR]
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import euler_tpu
from euler_tpu import train as train_lib
from euler_tpu.datasets import PPI, build_ppi
from euler_tpu.models import SupervisedGraphSage


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data_dir", default="/tmp/euler_tpu_ppi")
    ap.add_argument("--steps", type=int, default=2000)
    ap.add_argument("--batch_size", type=int, default=512)
    args = ap.parse_args()

    build_ppi(args.data_dir)
    graph = euler_tpu.Graph(directory=args.data_dir)
    model = SupervisedGraphSage(
        label_idx=0,
        label_dim=PPI["label_dim"],
        metapath=[[0], [0]],
        fanouts=[10, 10],
        dim=256,
        feature_idx=1,
        feature_dim=PPI["feature_dim"],
        max_id=PPI["num_nodes"] - 1,
    )

    def source(step):
        return np.asarray(graph.sample_node(args.batch_size, -1))

    state, history = train_lib.train(
        model,
        graph,
        source,
        num_steps=args.steps,
        optimizer="adam",
        learning_rate=0.01,
        log_every=100,
        prefetch_threads=4,
        prefetch_depth=3,
    )
    print("final:", history[-1])


if __name__ == "__main__":
    main()
