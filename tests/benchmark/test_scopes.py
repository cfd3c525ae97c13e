"""The readers PR 27 added: device time by the program's named scopes, on
a small capture recorded on the chip with the compiled step's HLO text
beside it (two steps of reddit_device_train, TPU v5 lite, PR 27: device
ops and the eg_align stamp only) and on texts and intervals worked by
hand; and the host-span readers on a hand-built ``Context``."""

import os

import pytest

from benchmark import harness, scopes, spans, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "benchmark", "data")
RECORDED = os.path.join(DATA, "reddit_device_train_2steps.xplane.pb")
RECORDED_HLO = os.path.join(DATA, "reddit_device_train_step.hlo.txt")
LAYERS = os.path.join(ROOT, "benchmark", "layers")


def reader(name):
    return harness.load_module(
        os.path.join(LAYERS, name + ".py"),
        "test_layer_" + name.replace(".", "_"))


# ---------------------------------------------------------------------------
# op_name -> scope, HLO text -> table
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op_name,scope", [
    ("jit(train_step)/jvp(M)/gather_features/gather", "gather_features"),
    ("jit(train_step)/transpose(jvp(M))/encoder/aggregate/dense/Dense_0/"
     "dot_general", "dense"),                      # innermost wins
    ("jit(train_step)/jvp(M)/aggregate/reduce_sum", "aggregate"),
    ("jit(train_step)/optimizer/jit(_where)/select_n", "optimizer"),
    ("jit(train_step)/transpose(jvp(M))/loss/mul;"
     "jit(train_step)/jvp(M)/dense/add", "loss"),  # a fusion: its root's
    ("jit(train_step)/jvp(M)/draw/draw/pallas_call", "draw"),
    ("jit(train_step)/convert_element_type", None),
    ("jit(train_step)/jvp(M)/densely/add", None),   # whole components only
    ("", None),
])
def test_scope_of_op_name(op_name, scope):
    assert scopes.scope_of_op_name(op_name) == scope


# ---------------------------------------------------------------------------
# which names are scopes: the readers say, as data
# ---------------------------------------------------------------------------


def test_every_scope_of_the_program_is_claimed_by_exactly_one_reader():
    """In place of the old pin (a tuple here equal to the program's): the
    universe is what the readers under layers/ declare. Each scope of the
    program has one reader, so the scope metrics, ``step.unscoped_ms``
    and ``mesh.collective_ms`` add up to ``step.device_busy_ms``; a
    scope claimed twice is refused where the declarations are read."""
    from euler_tpu import trace as TR

    claimed = scopes.declared_scopes()
    assert set(TR.STEP_SCOPES) <= set(claimed)
    assert scopes.STEP_HLO_FILE == TR.STEP_HLO_FILE
    assert scopes.UNSCOPED not in claimed and "collective" not in claimed
    for scope, name in claimed.items():
        assert scope in reader(name).SCOPES
    # every reader that declares scopes is a metric of the manifest
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        metrics = {m["name"] for m in json.load(f)["per_layer"]}
    assert set(claimed.values()) <= metrics


def _layers_with(tmp_path, **files):
    d = tmp_path / "layers"
    d.mkdir()
    for fn in os.listdir(LAYERS):
        if fn.endswith(".py"):
            (d / fn).write_text(open(os.path.join(LAYERS, fn)).read())
    for name, text in files.items():
        (d / (name.replace("__", ".") + ".py")).write_text(text)
    return str(d)


def test_a_new_reader_file_opens_the_set(tmp_path):
    """A PR that names ``store_read`` and ``store_update`` in the
    program's step adds a reader file that claims them; nothing else."""
    d = _layers_with(tmp_path, step__store_ms=(
        "from benchmark import scopes\n\n"
        'SCOPES = ("store_read", "store_update")\n\n\n'
        "def read(ctx):\n    return scopes.scopes_ms(ctx, *SCOPES)\n"))
    universe = scopes.declared_scopes(d)
    assert universe["store_read"] == universe["store_update"] == \
        "step.store_ms"
    assert set(scopes.declared_scopes()) < set(universe)
    path = "jit(train_step)/transpose(jvp(M))/store_update/scatter-add"
    assert scopes.scope_of_op_name(path) is None
    assert scopes.scope_of_op_name(path, universe) == "store_update"
    table = scopes.parse_hlo_scopes(
        'ENTRY %main (a: f32[8]) -> f32[8] {\n'
        '  %scatter.1 = f32[8]{0} scatter(%a), metadata={op_name="' + path
        + '"}\n}\n', universe)
    assert table["scatter.1"] == "store_update"


def test_a_scope_claimed_twice_is_refused(tmp_path):
    d = _layers_with(tmp_path, step__again_ms='SCOPES = ("dense",)\n')
    with pytest.raises(ValueError, match="claimed by both"):
        scopes.declared_scopes(d)


def test_the_old_name_is_the_readers_union_in_any_order():
    from euler_tpu import trace as TR

    assert scopes.STEP_SCOPES == TR.STEP_SCOPES
    assert scopes.STEP_SCOPES == tuple(reversed(TR.STEP_SCOPES))
    assert scopes.STEP_SCOPES != TR.STEP_SCOPES[1:]
    with pytest.raises(AttributeError):
        scopes.NO_SUCH_NAME


HLO_BY_HAND = """\
HloModule jit_train_step, is_scheduled=true, entry_computation_layout={(f32[8,4]{1,0:T(8,128)})->f32[8,4]{1,0}}

%fused_computation.1 (param_0.1: f32[8,4], param_1.2: f32[8,4]) -> f32[8,4] {
  %param_0.1 = f32[8,4]{1,0:T(8,128)} parameter(0)
  %param_1.2 = f32[8,4]{1,0:T(8,128)} parameter(1)
  %mul.1 = f32[8,4]{1,0} multiply(%param_0.1, %param_1.2), metadata={op_name="jit(train_step)/jvp(M)/aggregate/mul"}
  %add.1 = f32[8,4]{1,0} add(%mul.1, %param_1.2), metadata={op_name="jit(train_step)/jvp(M)/aggregate/add"}
  ROOT %neg.1 = f32[8,4]{1,0} negate(%add.1), metadata={op_name="jit(train_step)/jvp(M)/loss/neg"}
}

ENTRY %main.9 (Arg_0.1: f32[8,4]) -> f32[8,4] {
  %Arg_0.1 = f32[8,4]{1,0:T(8,128)} parameter(0), metadata={op_name="state[\\'consts\\'][\\'features\\']"}
  %copy.41 = f32[8,4]{0,1:T(8,128)} copy(%Arg_0.1)
  %fusion.3 = f32[8,4]{1,0} fusion(%copy.41, %Arg_0.1), kind=kLoop, calls=%fused_computation.1
  %fusion.4 = f32[8,4]{1,0} fusion(%fusion.3, %Arg_0.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/optimizer/add"}
  ROOT %custom-call.2 = f32[8,4]{1,0} custom-call(%fusion.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(M)/draw/pallas_call"}
}
"""


def test_hlo_text_to_scope_table_by_hand():
    table = scopes.parse_hlo_scopes(HLO_BY_HAND)
    assert table["copy.41"] == "unscoped"      # no metadata: the compiler's
    assert table["Arg_0.1"] == "unscoped"      # a parameter's name is no scope
    assert table["fusion.3"] == "aggregate"    # most of its fused computation
    assert table["fusion.4"] == "optimizer"    # its own op_name comes first
    assert table["custom-call.2"] == "draw"
    assert table["mul.1"] == "aggregate" and table["neg.1"] == "loss"


def test_lane_scope_seconds_by_hand():
    table = scopes.parse_hlo_scopes(HLO_BY_HAND)
    lane = xplane.DeviceLane(0, [
        ("%copy.41 = f32[8,4]{0,1:T(8,128)} copy(f32[8,4] %Arg_0.1)", 0., 100.),
        ("%fusion.3 = f32[8,4]{1,0} fusion(...)", 100., 130.),
        ("%fusion.4 = f32[8,4]{1,0} fusion(...)", 140., 150.),
        ("%custom-call.2 = f32[8,4] custom-call(...)", 150., 170.),
        ("%all-reduce.18 = (f32[4]) all-reduce(...)", 170., 175.),
        ("%unknown.7 = f32[] constant(0)", 175., 176.),
    ])
    got = scopes.lane_scope_seconds(lane, table)
    assert got == {
        "unscoped": pytest.approx(101e-9), "aggregate": pytest.approx(30e-9),
        "optimizer": pytest.approx(10e-9), "draw": pytest.approx(20e-9),
        "collective": pytest.approx(5e-9)}
    assert sum(got.values()) == pytest.approx(lane.busy_ns() * 1e-9)


def test_hlo_text_is_looked_for_beside_the_capture(tmp_path):
    x = tmp_path / "plugins" / "profile" / "2026_09_30" / "host.xplane.pb"
    assert scopes.hlo_path_for(str(x)) == str(
        tmp_path / scopes.STEP_HLO_FILE)
    assert scopes.scope_table(str(tmp_path / "none.txt")) is None


# ---------------------------------------------------------------------------
# the recorded capture and the compiled step's text from the same run
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED_HLO) as f:
        table = scopes.parse_hlo_scopes(f.read())
    return xplane.read_capture(RECORDED), table


def test_recorded_step_by_scope(recorded):
    """One step of reddit_device_train, 146 device ops, summed per scope
    by hand from the capture's event list (nanoseconds)."""
    cap, table = recorded
    lane = cap.fullest()
    assert len(lane.events) == 292  # 146 ops a step x 2
    step1 = xplane.DeviceLane(0, lane.events[:146])
    got = {k: v * 1e9
           for k, v in scopes.lane_scope_seconds(step1, table).items()}
    assert got == {
        "unscoped": pytest.approx(15_845_730, abs=2),
        "gather_features": pytest.approx(195_755, abs=2),
        "aggregate": pytest.approx(181_695, abs=2),
        "draw": pytest.approx(155_640, abs=2),
        "gather_labels": pytest.approx(53_511, abs=2),
        "dense": pytest.approx(10_285, abs=2),
        "loss": pytest.approx(2_500, abs=2),
        "optimizer": pytest.approx(55, abs=2),
    }
    assert sum(got.values()) == pytest.approx(step1.busy_ns(), rel=1e-9)


def test_recorded_scopes_add_up_to_the_busy_time(recorded):
    cap, table = recorded
    lane = cap.fullest()
    sec = scopes.lane_scope_seconds(lane, table)
    assert set(sec) <= set(scopes.declared_scopes()) | {"unscoped", "collective"}
    assert sum(sec.values()) == pytest.approx(lane.busy_ns() * 1e-9, rel=1e-9)
    assert cap.busy_s == pytest.approx(0.032889798, rel=1e-6)


def test_recorded_whole_table_copy_is_unscoped_and_the_kernel_is_draw(recorded):
    """The 15.8 ms layout change of the feature table is the compiler's:
    derived from the table's parameter, whose ``op_name`` is the
    argument's path and names no scope."""
    cap, table = recorded
    assert table["copy.41"] == "unscoped"
    copy = cap.fullest().op_seconds(
        __import__("re").compile(r"^%copy\.41 = f32\[2090001,602\]"))
    assert sum(copy.values()) == pytest.approx(2 * 0.0158438, rel=1e-3)
    # the draw kernel sits under the draw scope, with its key derivation
    (kernel,) = cap.fullest().op_seconds(xplane.DRAW_KERNEL)
    assert table[kernel.split(" ", 1)[0].lstrip("%")] == "draw"
    assert table["fusion.3"] == "gather_features"   # the hop-2 gather
    assert table["fusion.1"] == "gather_labels"
    assert table["reduce.2"] == "aggregate"


def test_scope_readers_on_the_recorded_capture(recorded, tmp_path):
    """The five device readers through a Context laid out as a run leaves
    it: the capture under plugins/profile/<run>/, the text beside it."""
    import shutil

    run = tmp_path / "plugins" / "profile" / "2026_09_30_17_44_00"
    run.mkdir(parents=True)
    shutil.copy(RECORDED, run / "runsc.xplane.pb")
    shutil.copy(RECORDED_HLO, tmp_path / scopes.STEP_HLO_FILE)
    ctx = _ctx(xplane_path=str(run / "runsc.xplane.pb"), trace_steps=2)
    got = {n: reader(n).read(ctx) for n in (
        "step.gather_ms", "step.dense_ms", "step.optimizer_ms",
        "step.unscoped_ms", "draw.scope_ms")}
    assert got["step.gather_ms"] == pytest.approx(0.2495, abs=1e-3)
    assert got["step.dense_ms"] == pytest.approx(0.1945, abs=1e-3)
    assert got["step.optimizer_ms"] == pytest.approx(5.5e-5, abs=1e-5)
    assert got["step.unscoped_ms"] == pytest.approx(15.8453, abs=1e-3)
    assert got["draw.scope_ms"] == pytest.approx(0.1556, abs=1e-3)
    busy_ms = ctx.capture.fullest().busy_ns() * 1e-6 / 2
    assert sum(got.values()) == pytest.approx(busy_ms, rel=1e-9)
    # the kernel alone is no more than its scope
    assert reader("draw.kernel_ms").read(ctx) <= got["draw.scope_ms"]


# ---------------------------------------------------------------------------
# hand-built contexts: every new reader, and the program that lacks it
# ---------------------------------------------------------------------------


def _ctx(phases_open=None, phases_close=None, events=(), **kw):
    base = dict(
        at_open={"phases": phases_open or {}, "compiles": 0},
        at_close={"phases": phases_close or {}, "compiles": 0},
        phase_events=list(events), steps=100, window_s=2.0,
        xplane_path=None, trace_steps=2, chips=1)
    base.update(kw)
    return harness.Context(**base)


def test_span_histogram_readers_on_a_hand_built_context():
    ctx = _ctx(
        phases_open={"dispatch": (10, 1000), "fence": (10, 20_000),
                     "log_flush": (0, 0), "input_other": (10, 500),
                     "stall": (1, 70_000)},
        phases_close={"dispatch": (110, 31_000), "fence": (110, 420_000),
                      "log_flush": (5, 50_000), "input_other": (110, 10_500),
                      "stall": (3, 310_000)})
    assert reader("trainer.dispatch_ms").read(ctx) == pytest.approx(0.3)
    assert reader("trainer.fence_wait_ms").read(ctx) == pytest.approx(4.0)
    # 50 ms of flushes over the window's 100 steps
    assert reader("trainer.log_flush_ms").read(ctx) == pytest.approx(0.5)
    assert reader("input.other_ms").read(ctx) == pytest.approx(0.1)
    # 240 ms of journalled excess in a 2 s window
    assert reader("trainer.stall_share").read(ctx) == pytest.approx(12.0)


def test_a_window_without_a_stall_reads_zero_not_nothing():
    ctx = _ctx(phases_open={"stall": (0, 0)}, phases_close={"stall": (0, 0)})
    assert reader("trainer.stall_share").read(ctx) == 0.0


SPANS_BY_HAND = [
    # the step the recorder was switched on in: its fence only. Left out
    ("step", 0, 1000, 6, "MainThread"),
    ("fence", 900, 90, 6, "MainThread"),
    # step 7: 1000..2000 µs; leaves cover 1000..1990 but for 1500..1504
    ("step", 1000, 1000, 7, "MainThread"),
    ("input_other", 1000, 10, 7, "MainThread"),
    ("input_stall", 1010, 5, 7, "MainThread"),
    ("input_other", 1015, 5, 7, "MainThread"),
    ("dispatch", 1020, 480, 7, "MainThread"),
    ("fence", 1504, 396, 7, "MainThread"),
    ("host_other", 1900, 90, 7, "MainThread"),
    # step 8: 2000..3000; one leaf hangs over the step's end: clipped
    ("step", 2000, 1000, 8, "MainThread"),
    ("dispatch", 2000, 500, 8, "MainThread"),
    ("fence", 2500, 600, 8, "MainThread"),
    # a worker's span covers nothing of the training thread
    ("sample", 1000, 2000, 8, "prefetch-0"),
]


def test_unspanned_is_the_step_less_the_union_of_its_leaves():
    # step 7: 4 + 10 µs bare; step 8: none -> 7 µs a step
    assert spans.unspanned_ms(SPANS_BY_HAND) == pytest.approx(0.007)
    ctx = _ctx(phases_close={"input_other": (2, 20)}, events=SPANS_BY_HAND)
    assert reader("trainer.unspanned_ms").read(ctx) == pytest.approx(0.007)
    # a record of one step has no later one to stand on: it is read whole
    assert spans.unspanned_ms(SPANS_BY_HAND[:2]) == pytest.approx(0.910)


def test_launch_gap_and_fence_return_by_hand():
    # profiler ns = monotonic ns - 1e6; two ops a step, two traced steps
    lane = xplane.DeviceLane(0, [
        ("%a", 24_000., 400_000.), ("%b", 400_000., 880_000.),   # step 7
        ("%a", 1_030_000., 1_400_000.), ("%b", 1_400_000., 2_050_000.),
    ])
    cap = xplane.Capture([lane], align_offset_ns=1_000_000.0)
    launch, ret = spans.edge_gaps_ms(cap, SPANS_BY_HAND, 2)
    # step 7: dispatch starts 1020 µs = 20,000 ns on the profiler's clock
    assert launch == [pytest.approx(0.004), pytest.approx(0.030)]
    # step 7: fence ends 1900 µs = 900,000 ns; last op ends 880,000
    assert ret == [pytest.approx(0.020), pytest.approx(0.050)]
    ctx = _ctx(events=SPANS_BY_HAND)
    ctx._capture = cap
    assert reader("trainer.launch_gap_ms").read(ctx) == pytest.approx(0.017)
    assert reader("trainer.fence_return_ms").read(ctx) == pytest.approx(0.035)
    # an unaligned capture places no host span; a lane that does not
    # divide into the traced steps has no steps to read
    assert spans.edge_gaps_ms(xplane.Capture([lane], None),
                              SPANS_BY_HAND, 2) is None
    assert spans.edge_gaps_ms(cap, SPANS_BY_HAND, 3) is None
    # a device clock that runs early by more than the host's gap between
    # two steps: step 8's first op "starts" before step 7's fence returned
    # and is still step 8's; the gaps turn negative, their sum stays
    early = xplane.Capture([lane], align_offset_ns=1_000_000.0 - 140_000.0)
    launch, ret = spans.edge_gaps_ms(early, SPANS_BY_HAND, 2)
    assert launch == [pytest.approx(-0.136), pytest.approx(-0.110)]
    assert ret == [pytest.approx(0.160), pytest.approx(0.190)]


NEW_READERS = [
    "trainer.dispatch_ms", "trainer.fence_wait_ms", "trainer.log_flush_ms",
    "trainer.unspanned_ms", "trainer.launch_gap_ms",
    "trainer.fence_return_ms", "trainer.stall_share", "input.other_ms",
    "step.gather_ms", "step.dense_ms", "step.optimizer_ms",
    "step.unscoped_ms", "draw.scope_ms",
]


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_is_silent_on_a_program_from_before_this_pr(name):
    """The parent's histograms, its spans (`device` and `host` on the
    training thread, no leaf) and a capture with no HLO text beside it:
    every new reader returns nothing and does not raise."""
    old = {k: (100, 1000) for k in (
        "input_stall", "sample", "h2d", "device", "host", "step", "compile")}
    events = [("device", 1000, 900, 7, "MainThread"),
              ("host", 1900, 100, 7, "MainThread"),
              ("step", 1000, 1000, 7, "MainThread")]
    ctx = _ctx(phases_open=old, phases_close=old, events=events,
               xplane_path=RECORDED)
    ctx._capture = xplane.read_capture(RECORDED)
    assert os.path.isfile(RECORDED_HLO)
    assert not os.path.isfile(scopes.hlo_path_for(RECORDED))
    assert reader(name).read(ctx) is None


def test_every_new_metric_is_in_the_manifest_with_a_reader():
    m = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    by_name = {x["name"]: x for x in m["per_layer"]}
    for name in NEW_READERS:
        assert os.path.isfile(os.path.join(LAYERS, name + ".py")), name
        assert by_name[name]["moves"] == "edges_per_s_chip"
        assert by_name[name]["workloads"], name
    assert "reddit_host_train" not in by_name["draw.scope_ms"]["workloads"]
