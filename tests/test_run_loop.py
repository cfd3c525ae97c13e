"""CLI driver tests (reference run_loop.py modes + model dispatch)."""

import os

import numpy as np
import pytest

from euler_tpu.run_loop import build_model, define_flags, main

COMMON = [
    "--max_id", "16", "--feature_idx", "0", "--feature_dim", "2",
    "--label_idx", "2", "--label_dim", "3", "--train_edge_type", "0,1",
    "--all_edge_type", "0,1", "--fanouts", "3,2", "--dim", "8",
    "--batch_size", "8", "--num_epochs", "4", "--log_steps", "2",
]


def _args(fixture_dir, model_dir, *extra):
    return ["--data_dir", fixture_dir, "--model_dir", model_dir] + COMMON + \
        list(extra)


def test_train_eval_save_cycle(fixture_dir, tmp_path):
    ck = str(tmp_path / "ck")
    assert main(_args(fixture_dir, ck, "--model", "graphsage_supervised",
                      "--mode", "train")) == 0
    assert os.path.isdir(ck)
    assert main(_args(fixture_dir, ck, "--model", "graphsage_supervised",
                      "--mode", "evaluate")) == 0
    assert main(_args(fixture_dir, ck, "--model", "graphsage_supervised",
                      "--mode", "save_embedding")) == 0
    emb = np.load(os.path.join(ck, "embedding.npy"))
    assert emb.shape == (17, 8)
    ids = np.loadtxt(os.path.join(ck, "id.txt"), dtype=np.int64)
    assert len(ids) == 17
    # relaunching train against the finished checkpoint resumes at
    # num_steps, trains 0 new steps, and must exit cleanly instead of
    # re-saving the restored step (orbax StepAlreadyExistsError)
    assert main(_args(fixture_dir, ck, "--model", "graphsage_supervised",
                      "--mode", "train")) == 0
    # frozen saved-embedding classifier trains from the export (fresh
    # checkpoint dir; the embedding comes from the previous run's export)
    assert main(_args(fixture_dir, str(tmp_path / "ck_cls"),
                      "--model", "saved_embedding", "--mode", "train",
                      "--num_epochs", "2",
                      "--embedding_file",
                      os.path.join(ck, "embedding.npy"))) == 0


def test_shared_graph_mode(fixture_dir, tmp_path):
    reg = str(tmp_path / "reg")
    os.makedirs(reg)
    rc = main(_args(fixture_dir, str(tmp_path / "ck2"),
                    "--model", "graphsage_supervised", "--mode", "train",
                    "--graph_mode", "shared", "--registry", reg,
                    "--num_processes", "1", "--num_epochs", "2"))
    assert rc == 0
    assert os.listdir(reg) == []  # service stopped + deregistered


def test_gcn_device_sampling_cli(fixture_dir, tmp_path):
    """--device_sampling reaches the full-neighbor GCN: train + evaluate
    run with the multi-hop expansion on device."""
    ck = str(tmp_path / "ck_gcn_dev")
    assert main(_args(fixture_dir, ck, "--model", "gcn",
                      "--mode", "train", "--device_sampling", "true",
                      "--num_epochs", "2")) == 0
    assert main(_args(fixture_dir, ck, "--model", "gcn",
                      "--mode", "evaluate", "--device_sampling",
                      "true")) == 0


def test_feature_dtype_cli(fixture_dir, tmp_path, graph):
    """--feature_dtype bfloat16 is threaded to the model as a real kwarg
    (no process-global state) and the run trains end-to-end."""
    args = define_flags().parse_args(
        COMMON + ["--model", "graphsage_supervised",
                  "--device_features", "true",
                  "--feature_dtype", "bfloat16"]
    )
    model = build_model(args, graph)
    assert model.feature_dtype == "bfloat16"
    assert "EULER_TPU_FEATURE_DTYPE" not in os.environ

    ck = str(tmp_path / "ck_bf16")
    assert main(_args(fixture_dir, ck, "--model", "graphsage_supervised",
                      "--mode", "train", "--device_features", "true",
                      "--feature_dtype", "bfloat16",
                      "--num_epochs", "2")) == 0
    assert "EULER_TPU_FEATURE_DTYPE" not in os.environ


@pytest.mark.parametrize(
    "name",
    ["line", "node2vec", "graphsage", "graphsage_supervised",
     "scalable_sage", "scalable_gcn", "gat", "gcn"],
)
def test_model_dispatch(name, graph):
    args = define_flags().parse_args(
        COMMON + ["--model", name, "--all_node_type", "-1"]
    )
    model = build_model(args, graph)
    batch = model.sample(graph, np.asarray(graph.sample_node(8, -1)))
    assert isinstance(batch, dict) and batch


def test_walk_trials_cli(graph):
    """--walk_trials is threaded to the Node2Vec module (the rejection
    walk's per-step proposal budget on the device alias path)."""
    args = define_flags().parse_args(
        COMMON + ["--model", "node2vec", "--all_node_type", "-1",
                  "--walk_p", "0.25", "--walk_q", "4.0",
                  "--walk_trials", "16", "--device_sampling", "true",
                  "--device_features", "true", "--feature_idx", "-1"]
    )
    model = build_model(args, graph)
    assert model.module.walk_trials == 16


def test_train_streamed_remote_data(fixture_dir, tmp_path, monkeypatch):
    """--stream true trains off a remote URL with zero local staging
    (the scratch-poor-host path; DEPLOY.md 'Remote data')."""
    import fsspec

    fs = fsspec.filesystem("memory")
    for name in os.listdir(fixture_dir):
        with open(os.path.join(fixture_dir, name), "rb") as f:
            data = f.read()
        with fs.open(f"/rl_stream/{name}", "wb") as f:
            f.write(data)
    cache = str(tmp_path / "never_staged")
    monkeypatch.setenv("EULER_TPU_CACHE", cache)
    try:
        rc = main(_args("memory://rl_stream", str(tmp_path / "ck_stream"),
                        "--stream", "true",
                        "--model", "graphsage_supervised",
                        "--mode", "train"))
        assert rc == 0
        assert not os.path.exists(cache)
    finally:
        fs.rm("/rl_stream", recursive=True)


def test_stream_rejected_outside_local_mode(fixture_dir, tmp_path):
    """--stream must never be dropped silently: shared/remote modes
    stage deliberately, so the flag errors out loudly there."""
    with pytest.raises(ValueError, match="graph_mode=local"):
        main(_args(fixture_dir, str(tmp_path / "ck"),
                   "--stream", "true", "--graph_mode", "shared",
                   "--registry", str(tmp_path / "reg"),
                   "--model", "graphsage_supervised", "--mode", "train"))


def test_metrics_every_writes_jsonl(fixture_dir, tmp_path):
    """--metrics_every=N appends one telemetry snapshot line per N
    training steps to the JSONL file (OBSERVABILITY.md emission), the
    snapshots carry the step-phase histograms + input_stall_ms, and
    --trace_file exports a valid Chrome trace with the phase slices."""
    import json

    from euler_tpu import telemetry as T

    T.telemetry_reset()
    mf = str(tmp_path / "metrics.jsonl")
    tf = str(tmp_path / "run_trace.json")
    assert main(_args(fixture_dir, str(tmp_path / "ck_metrics"),
                      "--model", "graphsage_supervised", "--mode", "train",
                      "--num_epochs", "2",
                      "--metrics_every", "2", "--metrics_file", mf,
                      "--trace_file", tf)) == 0
    lines = [json.loads(x) for x in open(mf)]
    assert lines, "no metrics emitted"
    assert all(rec["step"] % 2 == 0 for rec in lines)
    assert all("counters" in rec and "ops" in rec for rec in lines)
    # the step-phase profiler reported through the same snapshots
    last = lines[-1]
    assert {"input_stall", "sample", "device", "host",
            "step"} <= set(last["phases"]), last["phases"]
    assert last["input_stall_ms"] >= 0.0
    assert last["prefetch"]["mean_queue_depth"] >= 0.0
    # per-step step-phase counts: every step recorded every loop phase
    # (the snapshot hook fires mid-body, before that step's host/step
    # records land — hence the ±1)
    steps = last["phases"]["step"]["count"]
    assert steps >= last["step"] - 1
    assert steps <= last["phases"]["device"]["count"] <= steps + 1
    # the trace file is a valid Chrome trace whose phase lanes cover
    # the training loop (h2d rides the prefetch workers here:
    # device_prefetch on a 1-device CPU mesh stays enabled)
    from euler_tpu.trace import validate_chrome_trace

    with open(tf) as f:
        events = validate_chrome_trace(json.load(f))
    names = {e["name"] for e in events if e.get("cat") == "phase"}
    assert {"input_stall", "input_other", "sample", "h2d", "dispatch",
            "fence", "hook", "host_other", "step"} <= names, names
    # the parents `device` and `host` are sums of those leaves, kept as
    # histograms (asserted above): as slices they would lie over them
    assert not {"device", "host", "stall"} & names, names
