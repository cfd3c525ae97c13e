"""The contract the benchmark's store-family adapter relies on
(``benchmark/configs/scalable_sage_reddit_reference.py``): it builds the
state itself, ``[num_nodes + 1, dim]`` float32 tables under ``stores`` and
``grad_stores``, hands it to ``train(state=)``, and at steps 1 to 3 reads
``state`` out of ``train()``'s frame and subtracts its own start from
those leaves. Whatever the program does to the tables' device layout on
the way (parallel/mesh.py pins them rows-major), their logical shape and
dtype there are the adapter's. Toy node count, every width the
configuration's; nothing under ``benchmark/`` is edited."""

import json
import os
import time

import numpy as np
import pytest

from benchmark import check, harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
CONFIG = "scalable_sage_reddit"
CELL = "reddit_scalable_device_train"
NODES, BATCH = 400, 64


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """The real cell's manifest beside a copy of its configuration at
    ``NODES`` nodes, device-sampled and host-sampled."""
    d = tmp_path_factory.mktemp("store_contract")
    m = harness.load_json(MANIFEST)
    (entry,) = [c for c in m["configs"] if c["name"] == CONFIG]
    cfg = harness.load_json(os.path.join(ROOT, entry["file"]))
    cfg["graph"].update(num_nodes=NODES, num_partitions=2)
    cfg["flags"]["max_id"] = NODES - 1
    cfg["batch_size"] = BATCH
    cfg["limits"]["draw_skew"] = 0.2   # 256 draws a step, not 4,000
    for key in ("reference", "costs"):
        cfg[key] = os.path.join(ROOT, cfg[key])
    with open(d / "toy.json", "w") as f:
        json.dump(cfg, f)
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    m["configs"] = [dict(entry, file="toy.json")]
    m["workloads"] = [cell, dict(cell, name="host_sampled",
                                 traffic="train_host_sampled")]
    for x in m["per_layer"]:
        if CELL in x["workloads"]:
            x["workloads"] = [CELL, "host_sampled"]
    with open(d / "BENCHMARK.json", "w") as f:
        json.dump(m, f)
    return str(d / "BENCHMARK.json"), str(d / "data"), cfg


@pytest.mark.parametrize("cell", [CELL, "host_sampled"])
def test_adapter_reads_the_tables_it_built_from_trains_frame(toy, cell):
    path, data, cfg = toy
    prep = harness.Prepared(path, cell, time.time(), require_chip=False,
                            data_root=data)
    seen = []
    compared_state = prep.ref.compared_state

    def spy(state):
        for key in ("stores", "grad_stores"):
            (table,) = state[key]
            seen.append((key, table.shape, str(table.dtype),
                         tuple(table.format.layout.major_to_minor)))
        return compared_state(state)

    prep.ref.compared_state = spy
    try:
        hook = prep.drive(seed=2_147_483_659, seconds=0.2,
                          first_steps_only=True)
        numbers = prep.compare(hook)
    finally:
        prep.ref.compared_state = compared_state
        prep.close()
    shape = (NODES + 1, cfg["dim"])
    assert seen == [("stores", shape, "float32", (0, 1)),
                    ("grad_stores", shape, "float32", (0, 1))]
    # what the adapter handed over went through train()'s placement: the
    # start it kept is a host copy, and the rows it compares are changes
    # from that start
    start, end = hook.captured["start"], hook.captured["end"]
    assert start["store0"].shape == start["grad_store0"].shape == shape
    ref = prep.ref
    named = np.unique(np.concatenate(
        [np.asarray(i).reshape(-1) for hops in hook.captured["hops"]
         for i in hops]))
    assert end[ref.ROWS].shape == end[ref.GRAD_ROWS].shape == (
        len(named), cfg["dim"])
    assert np.abs(end[ref.ROWS]).max() > 0
    assert np.abs(end[ref.GRAD_ROWS]).max() > 0
    assert end[ref.OUTSIDE][0] == 0
    correct, table = check.verdict(numbers, dict(cfg["limits"]))
    assert correct, table
