"""The harness driven on the CPU at toy sizes: one cell end to end, the
refusal of a CPU run as a measurement, the control, and the timed path
broken underneath in each of the ways a training cell can break.

Everything runs in this process on the tests' virtual CPU devices, with
the harness's look for a chip skipped (``require_chip=False``); no time,
rate or share from these runs is ever printed under a metric's name.
"""

import os
import subprocess
import sys
import time

import pytest

from benchmark import check, harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, "BENCHMARK_toy.json")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(tmp_path_factory, cell, seed=5, trace=False, calibrate=False):
    data = tmp_path_factory.getbasetemp() / "benchmark_toy_data"
    return harness.run_cell(
        TOY, cell, seed, 0.2, trace, time.time(), require_chip=False,
        calibrate=calibrate, data_root=str(data),
        keep_trace=str(tmp_path_factory.mktemp("trace")) if trace else None,
    )


@pytest.mark.parametrize("cell", [
    "toy_device", "toy_ppi_device", "toy_host", "toy_dp4",
    "toy_store_device"])
def test_toy_cell_end_to_end(tmp_path_factory, cell):
    r = _run(tmp_path_factory, cell)
    assert RESULT_KEYS <= set(r)
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"
    # a CPU run is no measurement: nothing is printed under a metric's name
    assert r["metrics"] == {}
    assert list(r)[-1] == "compared"
    for row in r["compared"].values():
        assert set(row) == {"value", "limit"}


def test_traced_toy_run_reads_program_spans(tmp_path_factory):
    r = _run(tmp_path_factory, "toy_device", seed=9, trace=True)
    assert r["correct"] is True
    assert r["metrics"] == {}
    # the readers ran and found the program's spans; device readers are
    # silent without a device trace
    w = r["withheld_cpu"]
    assert {"trainer.step_ms_p50", "input.stall_ms", "engine.sample_ms",
            "trainer.fenced_dispatch_ms"} <= set(w)
    assert not {"step.device_busy_ms", "draw.kernel_ms",
                "step.mfu_roofline", "device.idle_share"} & set(w)


def test_recorder_stops_with_the_capture(tmp_path_factory):
    """The span recorder is a ring: a window that runs on for thousands
    of steps after the traced ones must not push their spans out of it.
    The harness stops it two steps after the capture's last."""
    import json

    keep = tmp_path_factory.mktemp("trace_long")
    data = tmp_path_factory.getbasetemp() / "benchmark_toy_data"
    r = harness.run_cell(
        TOY, "toy_host", 17, 5.0, True, time.time(), require_chip=False,
        data_root=str(data), keep_trace=str(keep))
    assert r["correct"] is True
    with open(os.path.join(keep, "phase_events.json")) as f:
        steps = [e[3] for e in json.load(f) if e[3] is not None]
    traffic = harness.Cell(TOY, "toy_host").traffic
    last = (traffic["warmup_steps"] + traffic["trace_offset_steps"]
            + traffic["trace_steps"])
    # train() numbers a step's spans from 0, the hook from 1; the
    # prefetch workers' spans run a queue's depth ahead of the thread's
    assert last - 1 <= max(steps) <= last + 20
    # the window went on without it. Its last step (the hook counts from
    # warm-up's end) has to lie well past the recorder's for the line
    # above to say anything; a test machine too loaded to get there in
    # five seconds shows nothing either way
    if traffic["warmup_steps"] + r["attempted"] <= last + 50:
        pytest.skip("the window closed with the capture: machine too slow")


def test_run_py_refuses_a_cpu_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "reddit_device_train", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=ROOT,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


@pytest.mark.parametrize("cell", ["toy_device", "toy_store_device"])
def test_control_fails_the_toy_limits(tmp_path_factory, cell):
    """The reference in bfloat16, put in the program's place, has to come
    out as not correct by at least one of the numbers."""
    r = _run(tmp_path_factory, cell, seed=11, calibrate=True)
    limits = harness.Cell(TOY, cell).cfg["limits"]
    ok, _ = check.verdict(r["calibration"]["control_bf16"], limits)
    assert not ok
    ok, _ = check.verdict(r["calibration"]["fault_half_batch"], limits)
    assert not ok


# ---- the timed path broken underneath ----

def _patch_step(monkeypatch, wrap, cls="Model"):
    from euler_tpu.models import base

    orig = getattr(base, cls).make_train_step

    def make(self, optimizer):
        return wrap(orig(self, optimizer))

    monkeypatch.setattr(getattr(base, cls), "make_train_step", make)


def _state_unchanged(monkeypatch):
    def wrap(step):
        def broken(state, batch):
            _, loss, metric = step(state, batch)
            return state, loss, metric
        return broken
    _patch_step(monkeypatch, wrap)


def _first_rows(frac):
    def plant(monkeypatch):
        import jax

        def wrap(step):
            def broken(state, batch):
                n = jax.tree_util.tree_leaves(batch)[0].shape[0]
                part = jax.tree_util.tree_map(lambda x: x[: n // frac], batch)
                return step(state, part)
            return broken
        _patch_step(monkeypatch, wrap)
    return plant


def _altered_draw(monkeypatch):
    from euler_tpu.graph import device as device_graph

    orig = device_graph.sample_fanout

    def altered(adjs, roots, key, counts):
        ids = list(orig(adjs, roots, key, counts))
        # one pick replaced by its own parent's id + 1 where it is produced
        ids[1] = ids[1].at[0].set((ids[0][0] + 1) % 1000)
        return ids

    monkeypatch.setattr(device_graph, "sample_fanout", altered)


def _store_unwritten(monkeypatch):
    """The store family's own fault: the fresh activations never reach
    the store (everything else of the step as it should be)."""
    def wrap(step):
        def broken(state, batch):
            new, loss, metric = step(state, batch)
            return dict(new, stores=state["stores"]), loss, metric
        return broken
    _patch_step(monkeypatch, wrap, "ScalableStoreModel")


def _stale_gradients_dropped(monkeypatch):
    """...and the gradient store never filled: the second optimizer then
    sees no gradient, and the parameters move by the first alone."""
    def wrap(step):
        def broken(state, batch):
            new, loss, metric = step(state, batch)
            return dict(new, grad_stores=state["grad_stores"]), loss, metric
        return broken
    _patch_step(monkeypatch, wrap, "ScalableStoreModel")


def _altered_loss(monkeypatch):
    def wrap(step):
        def broken(state, batch):
            new, loss, metric = step(state, batch)
            return new, loss * 1.01, metric
        return broken
    _patch_step(monkeypatch, wrap)


@pytest.mark.parametrize("cell,plant", [
    ("toy_device", _state_unchanged),
    ("toy_device", _first_rows(2)),
    ("toy_dp4", _first_rows(4)),
    ("toy_device", _altered_draw),
    ("toy_host", _altered_loss),
    ("toy_store_device", _store_unwritten),
    ("toy_store_device", _stale_gradients_dropped),
], ids=["state_unchanged", "half_batch_left_out", "exchange_left_out",
        "draw_altered", "loss_altered", "store_unwritten",
        "stale_gradients_dropped"])
def test_broken_timed_path_is_not_correct(tmp_path_factory, monkeypatch,
                                          cell, plant):
    plant(monkeypatch)
    r = _run(tmp_path_factory, cell, seed=13)
    assert r["correct"] is False, r["compared"]
