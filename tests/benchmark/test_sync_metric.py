"""``trainer.sync_ms`` by hand: what all fences of the window cost a
step, and nothing on a program that keeps no ``fence`` histogram."""

import json
import os

import pytest

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "trainer.sync_ms"


def _read(phases_open, phases_close, steps=100):
    reader = harness.load_module(
        os.path.join(ROOT, "benchmark", "layers", NAME + ".py"),
        "test_layer_trainer_sync_ms")
    return reader.read(harness.Context(
        at_open={"phases": phases_open, "compiles": 0},
        at_close={"phases": phases_close, "compiles": 0},
        phase_events=[], steps=steps, window_s=2.0, xplane_path=None,
        trace_steps=2, chips=1))


@pytest.mark.parametrize("fences, fence_us, steps, want_ms", [
    (3, 400, 100, 0.012),    # a fence every 32nd step
    (100, 800, 100, 0.8),    # a program that fences every step
    (0, 0, 100, 0.0),        # a window shorter than the sync interval
])
def test_sync_ms_is_the_fences_sum_over_the_windows_steps(
        fences, fence_us, steps, want_ms):
    got = _read({"fence": (5, 9_000), "step": (160, 400_000)},
                {"fence": (5 + fences, 9_000 + fences * fence_us),
                 "step": (160 + steps, 650_000)}, steps)
    assert got == pytest.approx(want_ms)


def test_sync_ms_is_silent_without_a_fence_histogram_or_a_window():
    assert _read({"step": (1, 5)}, {"step": (101, 900)}) is None
    assert _read({"fence": (1, 5)}, {"fence": (1, 5)}, steps=0) is None


def test_sync_ms_is_declared_for_the_cells_that_train():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    cells = entry.pop("workloads")
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "trainer",
        "moves": "edges_per_s_chip",
    }
    # every cell drives train() with the step phases on (a later cell
    # that does may join the list)
    assert {"reddit_device_train", "ppi_device_train", "reddit_host_train",
            "reddit_device_train_dp4",
            "reddit_scalable_device_train"} <= set(cells)
