"""train() where the model draws its batch on the device: up to
``train.CHUNK_STEPS`` steps a dispatch, through one jitted program whose
step count is an argument. The same losses, history, checkpoints and
final state, bit for bit, as the same call held to one step a dispatch;
a fed hook sees its first three steps one at a time and then each
chunk's end; the host-sampled path dispatches a step at a time; the
step is traced and lowered once."""

import jax
import numpy as np
import pytest

from euler_tpu import devprof
from euler_tpu import telemetry as T
from euler_tpu import train as train_lib
from euler_tpu.checkpoint import Checkpointer
from euler_tpu.graph import native
from euler_tpu.models import ScalableSage, SupervisedGraphSage

MAX_ID = 16  # fixture ids go up to 16
# none of them divides by ten: chunks are cut at each of them
NUM_STEPS, LOG_EVERY, CHECKPOINT_EVERY, PROFILE_STEPS = 57, 23, 17, (5, 31)
# where the chunks end: the hook's first three steps, the profiler's
# start (5) and stop (31), the log windows (23, 46), the checkpoints
# (17, 34, 51), the last step, and ten steps on from each in between
CHUNK_ENDS = [1, 2, 3, 5, 15, 17, 23, 31, 34, 44, 46, 51, 57]
# a resumed call: from the checkpoint at 57 on to 70, no fed first steps
RESUMED_STEPS, RESUMED_ENDS = 70, [67, 68, 70]
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
SAVE = Checkpointer.save


def _sage(device_sampling=True):
    return SupervisedGraphSage(
        label_idx=2, label_dim=3, metapath=[[0, 1], [0, 1]],
        fanouts=[3, 2], dim=16, feature_idx=0, feature_dim=2,
        max_id=MAX_ID, device_features=True,
        device_sampling=device_sampling,
    )


def _store():
    return ScalableSage(
        label_idx=2, label_dim=3, edge_type=[0, 1], fanout=3, num_layers=2,
        dim=16, max_id=MAX_ID, concat=True, feature_idx=0, feature_dim=2,
        device_features=True, device_sampling=True,
    )


MODELS = {"graphsage": _sage, "scalable_sage": _store}


@pytest.fixture(autouse=True)
def _clean_slate():
    T.telemetry_reset()
    T.set_telemetry(True)
    yield
    T.telemetry_reset()
    # a profiled run set the temporaries gauge, which the reset keeps
    native.lib().eg_devprof_set_step_temp(0)


def _train(model, graph, num_steps, seen, **kw):
    """Roots by the step number; the draws ride the batch's seed, a
    counter of the model: with the batches made in step order (one
    prefetch worker) a run repeats to the bit."""
    nodes = np.unique(graph.sample_node(256, -1))

    def hook(step, state=None, batch=None, loss=None):
        seen.append((step, np.asarray(loss).item(),
                     [np.asarray(x).tolist() for x in jax.tree.leaves(batch)]))

    return train_lib.train(
        model, graph,
        lambda step: np.random.default_rng(step).choice(nodes, 8),
        num_steps=num_steps, learning_rate=0.01, optimizer="adam",
        prefetch_threads=1, step_hook=hook, **kw)


def _run(make, graph, tmp_path, monkeypatch, chunk_steps):
    """Two calls, the second resumed from the first's last checkpoint:
    what the hook saw, the two histories, the checkpoints' steps and the
    final state on the host."""
    monkeypatch.setattr(train_lib, "CHUNK_STEPS", chunk_steps)
    model, seen, saved = make(), [], []

    def recording_save(self, step, state, force=False):
        saved.append(step)
        return SAVE(self, step, state, force=force)

    monkeypatch.setattr(Checkpointer, "save", recording_save)
    ckpt = tmp_path / f"ckpt{chunk_steps}"
    kw = dict(log_every=LOG_EVERY, checkpoint_dir=str(ckpt),
              checkpoint_every=CHECKPOINT_EVERY)
    _, first = _train(
        model, graph, NUM_STEPS, seen, **kw,
        profile_dir=str(tmp_path / f"profile{chunk_steps}"),
        profile_steps=PROFILE_STEPS)
    state, second = _train(model, graph, RESUMED_STEPS, seen, **kw)
    return seen, first + second, saved, jax.tree.map(np.asarray, state)


@pytest.mark.parametrize("family", sorted(MODELS))
def test_chunks_give_what_one_step_a_dispatch_gives(
        graph, tmp_path, monkeypatch, family):
    seen, history, saved, state = _run(
        MODELS[family], graph, tmp_path, monkeypatch, 10)
    seen1, history1, saved1, state1 = _run(
        MODELS[family], graph, tmp_path, monkeypatch, 1)
    # one step a dispatch: the hook sees every step
    assert [s[0] for s in seen1] == list(range(1, RESUMED_STEPS + 1))
    # chunked: the fed first steps one at a time, then each chunk's end
    assert [s[0] for s in seen] == CHUNK_ENDS + RESUMED_ENDS
    # each chunk's last step: its loss and its batch, bit for bit
    by_step = {s[0]: s for s in seen1}
    assert seen == [by_step[s[0]] for s in seen]
    # the log windows, their losses and metrics
    drop = lambda h: [{k: v for k, v in w.items() if k != "steps_per_sec"}
                      for w in h]
    assert len(history) == 4  # 23, 46, 57 | 70
    assert drop(history) == drop(history1)
    assert saved == saved1 == [17, 34, 51, 57, 68, 70]
    jax.tree.map(np.testing.assert_array_equal, state, state1)
    # the capture's text is of the program that ran: the chunk's loop
    text = (tmp_path / "profile10" / "train_step.hlo.txt").read_text()
    assert text.startswith("HloModule jit_train_step") and " while(" in text


@pytest.mark.parametrize("device_sampling, steps_per_dispatch", [
    (False, [1] * 12), (True, [1, 1, 1, 1, 4, 4])],
    ids=["host_sampled", "device_sampled"])
def test_the_steps_of_each_dispatch_are_counted(
        graph, device_sampling, steps_per_dispatch):
    """The value histogram ``dispatch_steps``: a sample a dispatch, its
    steps; the host-sampled path reads one step a dispatch."""
    seen = []
    _train(_sage(device_sampling), graph, 12, seen, log_every=4)
    assert [s[0] for s in seen] == list(np.cumsum(steps_per_dispatch))
    h = T.phase_hists()["dispatch_steps"]
    assert h["count"] == len(steps_per_dispatch)
    assert h["sum_us"] == 12


@pytest.mark.parametrize("profiled", [False, True],
                         ids=["unprofiled", "profiled"])
def test_the_step_is_traced_and_lowered_once(
        graph, tmp_path, monkeypatch, profiled):
    """One program serves every dispatch, a fed hook's single first
    steps included: the model's step function runs under a trace once,
    and one module is lowered under the step's name (a profiled run's
    HLO text is of that same lowering). A separate one-step jit beside
    the chunked program would trace and lower the step twice: seconds
    of set-up at the benchmark's sizes, in every run, since a compile
    cache skips neither."""
    model = _sage()
    traced = []
    make = model.make_train_step

    def counting(opt):
        step = make(opt)

        def train_step(state, batch):
            traced.append(1)
            return step(state, batch)

        return train_step

    monkeypatch.setattr(model, "make_train_step", counting)
    lowered = []

    def listener(event, duration, **kw):
        if event == LOWER_EVENT:
            lowered.append(devprof._fn_key(kw.get("fun_name")))

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        kw = dict(profile_dir=str(tmp_path), profile_steps=(4, 8)) \
            if profiled else {}
        _train(model, graph, 25, [], log_every=10, **kw)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert len(traced) == 1
    assert lowered.count("train_step") == 1
