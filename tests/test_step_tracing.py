"""What the train step names on both sides of the fence (OBSERVABILITY.md
"Step phases", "Step scopes", "Stall journal"): the named scopes of the
jitted step, the leaves that tile the training thread's iteration on one
clock, the kill-switch, and the stall journal."""

import gc
import statistics
import time

import jax
import numpy as np
import pytest

from euler_tpu import blackbox, devprof
from euler_tpu import telemetry as T
from euler_tpu import trace as TR
from euler_tpu import train as train_lib
from euler_tpu.graph import native
from euler_tpu.models import LINE, Node2Vec, ScalableSage, SupervisedGraphSage
from euler_tpu.parallel import make_mesh

MAX_ID = 16  # fixture ids go up to 16
# the scopes of the historical-store family's step alone (models/base.py
# ScalableStoreModel); every other scope is on GraphSAGE's step too
STORE_SCOPES = {"stores_read", "stores_write"}
# the scopes of the shallow embedding models' step alone (models/shallow.py)
WALK_SCOPES = {"walk", "negatives", "pair_rows"}
# the scopes of the full-neighbourhood family's step alone (models/gcn.py:
# graph/device.py multi_hop_neighbor and nn/sparse_aggregators.py)
EXPAND_SCOPES = {"expand", "segment_agg"}
# the scope of that family's attention aggregator alone
# (nn/sparse_aggregators.py _attend: --aggregator attention)
ATTENTION_SCOPES = {"edge_softmax"}
FAMILY_SCOPES = (STORE_SCOPES | WALK_SCOPES | EXPAND_SCOPES
                 | ATTENTION_SCOPES)
TRAIN_THREAD_LEAVES = {
    "input_stall", "input_other", "h2d",
    *(leaf for leaf, parent in T.PHASE_PARENT.items() if parent != "setup"),
}


def _loop_events(rec):
    """The training thread's events of the loop: what train() recorded
    on its way to the first iteration (the set-up leaves) lies before."""
    return [e for e in rec.events()
            if e[4] == "MainThread" and e[0] not in T.SETUP_PHASES]


@pytest.fixture(autouse=True)
def _clean_slate():
    T.telemetry_reset()
    T.set_telemetry(True)
    T.set_trace_sink(None)
    yield
    T.telemetry_reset()
    T.set_telemetry(True)
    T.set_trace_sink(None)


def _model(device_sampling=True):
    return SupervisedGraphSage(
        label_idx=2, label_dim=3, metapath=[[0, 1], [0, 1]],
        fanouts=[3, 2], dim=16, feature_idx=0, feature_dim=2,
        max_id=MAX_ID, device_features=True,
        device_sampling=device_sampling,
    )


def _train(graph, steps, source_fn=None, device_sampling=True, **kw):
    """Device-sampled (a chunk of steps a dispatch) unless told not."""
    kw.setdefault("log_every", 4)
    return train_lib.train(
        _model(device_sampling), graph,
        source_fn or (lambda s: graph.sample_node(8, -1)),
        num_steps=steps, learning_rate=0.01, optimizer="adam", **kw)


def _assert_leaves_tile(main, firsts):
    """Of the training thread's spans: every dispatch (labelled by its
    first step, ``firsts``) has its ``step`` span, and its leaves lie
    inside it, overlap nowhere and cover it."""
    steps = {e[3]: e for e in main if e[0] == "step"}
    assert sorted(steps) == list(firsts)
    for k, (_, s0, dur, _, _) in steps.items():
        leaves = sorted(
            (ts, ts + d) for name, ts, d, step, _ in main
            if name != "step" and step == k and d > 0)
        assert leaves[0][0] >= s0 and leaves[-1][1] <= s0 + dur
        for (_, e0), (s1, _) in zip(leaves, leaves[1:]):
            assert s1 >= e0, (k, leaves)  # no two leaves overlap
        covered = sum(e - s for s, e in leaves)
        assert covered >= 0.99 * dur, (k, covered, dur)


# ---------------------------------------------------------------------------
# (a) device side: the scopes of the jitted step
# ---------------------------------------------------------------------------


def test_lowered_train_step_holds_every_step_scope(graph):
    m = _model()
    opt = train_lib.get_optimizer("adam", 0.01)
    roots = graph.sample_node(8, -1)
    state = m.init_state(jax.random.PRNGKey(0), graph, roots, opt)
    text = jax.jit(m.make_train_step(opt)).lower(
        state, m.sample(graph, roots)).as_text(debug_info=True)
    for scope in TR.STEP_SCOPES:
        assert (f"/{scope}/" in text) == (scope not in FAMILY_SCOPES), scope
    # the backward pass rides its scope: no scope of its own is needed
    assert "transpose(jvp(" in text


def test_lowered_store_step_holds_every_step_scope(graph):
    """The store family's step: the single-hop draw under ``draw``, the
    stores' gathers and the clearing set under ``stores_read``, the
    scatter-add and the set of fresh rows under ``stores_write``, both
    Adams under ``optimizer``."""
    m = ScalableSage(
        label_idx=2, label_dim=3, edge_type=[0, 1], fanout=3, num_layers=2,
        dim=16, max_id=MAX_ID, concat=True, feature_idx=0, feature_dim=2,
        device_features=True, device_sampling=True,
    )
    opt = train_lib.get_optimizer("adam", 0.01)
    roots = graph.sample_node(8, -1)
    state = m.init_state(jax.random.PRNGKey(0), graph, roots, opt)
    text = jax.jit(m.make_train_step(opt)).lower(
        state, m.sample(graph, roots)).as_text(debug_info=True)
    for scope in (set(TR.STEP_SCOPES) - WALK_SCOPES - EXPAND_SCOPES
                  - ATTENTION_SCOPES):
        assert f"/{scope}/" in text, scope
    lines = text.splitlines()
    gathers = [ln for ln in lines if "stores_read" in ln and "gather" in ln]
    adds = [ln for ln in lines if "stores_write" in ln and "scatter" in ln]
    assert gathers and adds
    assert any("/optimizer/" in ln for ln in lines)
    assert not any("/stores_" in ln and "/optimizer/" in ln for ln in lines)


def _gcn_model(**kw):
    from euler_tpu.models import SupervisedGCN

    kw.setdefault("aggregator", "mean")
    kw.setdefault("max_nodes_per_hop", [32, 64])
    return SupervisedGCN(
        label_idx=2, label_dim=3, metapath=[[0, 1], [0, 1]], dim=16,
        max_edges_per_hop=[64, 256],
        feature_idx=0, feature_dim=2, max_id=MAX_ID,
        device_features=True, device_sampling=True, **kw,
    )


def test_lowered_gcn_step_holds_the_expansion_scopes(graph):
    """The full-neighbourhood family's step: all of the device expansion
    (slab-row gathers, the sort, the scatters) under ``expand``, the
    sparse aggregator's work over the edge list (the mask, the degree
    and the sum as reductions along the rows of a regular list; layer
    1's gather by ``dst``, whose transpose is the one scatter left) under
    ``segment_agg``, its matmuls under ``dense``, and under
    ``gather_features`` the rows: the
    roots' and hop 1's sets, and layer 0's messages of both hops, read
    from the stored table one a slot."""
    m = _gcn_model()
    assert m.step_counters == (
        "expand_slots", "expand_edges", "expand_overflow_nodes",
        "expand_gathered_slots")
    opt = train_lib.get_optimizer("adam", 0.01)
    roots = graph.sample_node(8, -1)
    state = m.init_state(jax.random.PRNGKey(0), graph, roots, opt)
    step = jax.jit(m.make_train_step(opt))
    text = step.lower(state, m.sample(graph, roots)).as_text(debug_info=True)
    here = EXPAND_SCOPES | {"gather_features", "gather_labels", "dense",
                            "loss", "optimizer"}
    for scope in TR.STEP_SCOPES:
        assert (f"/{scope}/" in text) == (scope in here), scope
    lines = text.splitlines()
    assert any("/expand/" in ln and "sort" in ln for ln in lines)
    # the rank's scatter and the set's: this model's caps bind (32 < 8 x
    # 5 slots, 64 < 32 x 5), so its masks read the rank
    assert sum("/expand/scatter" in ln for ln in lines) >= 2
    # the device expansion's list is regular: its degree and its sum are
    # row reductions, forward (the embed program holds no scatter under
    # segment_agg at all); the training step keeps one scatter there, the
    # transposed gather by ``dst`` of layer 1
    assert any("/segment_agg/reduce_sum" in ln for ln in lines)
    scatters = [ln for ln in lines
                if "/segment_agg/" in ln and "scatter" in ln]
    assert scatters and all("transpose(" in ln for ln in scatters)
    embed = jax.jit(m.make_embed_step()).lower(
        state, m.sample(graph, roots)).as_text(debug_info=True)
    assert "/segment_agg/reduce_sum" in embed
    assert not any("/segment_agg/" in ln and "scatter" in ln
                   for ln in embed.splitlines())
    assert any("/segment_agg/" in ln and "gather" in ln for ln in lines)
    assert any("/gather_features/" in ln and "gather" in ln for ln in lines)
    assert not any("/segment_agg/" in ln and "dot_general" in ln
                   for ln in lines)
    assert not any("/expand/" in ln and "/segment_agg/" in ln for ln in lines)
    # the step's counts leave beside the metric: slots, true edges, the
    # unique neighbours past a cap (none: the caps hold), and the slots
    # whose rows layer 0's messages read (all: hop 1's set of 32 rows
    # fits in one block, so both hops take one pass)
    _, _, (metric, counts) = step(state, m.sample(graph, roots))
    slots, edges, overflow, gathered = np.asarray(counts)
    assert metric.shape == (3,) and overflow == 0
    assert 0 < edges <= slots and gathered == slots


@pytest.mark.parametrize("one_pass", [True, False],
                         ids=["stored_table", "hops_rows"])
def test_lowered_gcn_step_gathers_the_outer_hops_rows_once(graph, one_pass):
    """Where layer 0's messages come from the stored table in one pass
    the step holds no tensor of the outer hop's set rows, at the feature
    width or the stored one, and no row gather of that many rows; the
    same model with a projected node encoder (``use_residual``) keeps
    the hop's rows and shows that the look would find them."""
    import re

    m = _gcn_model(use_residual=not one_pass)
    cap, slots = 64, 32 * 5  # the outer hop's set; hop 1's 32 x 5 slots
    opt = train_lib.get_optimizer("adam", 0.01)
    roots = graph.sample_node(8, -1)
    state = m.init_state(jax.random.PRNGKey(0), graph, roots, opt)
    text = jax.jit(m.make_train_step(opt)).lower(
        state, m.sample(graph, roots)).as_text(debug_info=True)
    assert f"tensor<{slots}xi32>" in text  # the slab of the fixture is 5 wide
    # an op's line ends in the number of its location; the location's own
    # line holds the scopes it was traced under
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    gathers = [
        (names.get(ref, ""), out)
        for out, ref in re.findall(
            r'"stablehlo\.gather".* -> (tensor<\S+>) loc\((#loc\d+)\)', text)]
    assert len(gathers) > 8
    set_rows = re.findall(rf"tensor<{cap}x(?:2|128)xf32>", text)
    row_gathers = [g for g in gathers if g[1].startswith(f"tensor<{cap}x")]
    assert bool(set_rows) == (not one_pass)
    assert bool(row_gathers) == (not one_pass)
    # the messages' rows, one a slot, under gather_features, by the ids
    # the expansion hands out: no gather composes them (``nodes[dst]``)
    assert any(
        "/gather_features/" in name and out == f"tensor<{slots}x128xf32>"
        for name, out in gathers) == one_pass
    assert not any(
        name.endswith("/segment_agg/gather")
        and out == f"tensor<{slots}xi32>"
        for name, out in gathers)


@pytest.mark.parametrize("hop2_cap", [200, 64], ids=["caps_hold",
                                                     "hop2_binds"])
def test_compiled_one_pass_gcn_step_drops_the_outer_hops_dedup(
        graph, caplog, hop2_cap):
    """The one-pass step reads the outer hop's slot ids and, where its
    cap cannot bind, a mask with no rank term: nothing then reads that
    hop's set, rank or sort, and the compiler's dead-code pass takes
    them out. 8 roots x 5 slots = 40 (hop 1 at cap 40, whose dedup layer
    1 reads through ``dst``), 40 x 5 = 200 slots in hop 2: at cap 200 the
    compiled step holds no sort and no scatter over ``s32[200]``; at cap
    64 both are there and the route log says the hop is ranked. Either
    step runs, and counts no id past a cap: the fixture's 17 ids fit
    both."""
    import logging
    import re

    from euler_tpu.graph import device as device_graph

    m = _gcn_model(max_nodes_per_hop=[40, hop2_cap])
    opt = train_lib.get_optimizer("adam", 0.01)
    roots = np.asarray(graph.sample_node(8, -1))
    state = m.init_state(jax.random.PRNGKey(0), graph, roots, opt)
    assert m.module._hop_rows_why(
        m.sample(graph, roots), state["consts"]) is None
    device_graph._log_expand_route.cache_clear()
    with caplog.at_level(logging.INFO, logger="euler_tpu"):
        step = jax.jit(m.make_train_step(opt))
        text = step.lower(state, m.sample(graph, roots)).compile().as_text()
    route = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("expand path:")]
    assert route and "8 -> 40 -> 200 slots" in route[0]
    outer = re.compile(r"s32\[200\]")
    sorts = [ln for ln in text.splitlines()
             if " sort(" in ln and outer.search(ln)]
    scatters = [ln for ln in text.splitlines()
                if " scatter(" in ln and outer.search(ln)]
    # hop 1's dedup stays: layer 1 reads its set through ``dst``
    assert any(" sort(" in ln and "s32[40]" in ln
               for ln in text.splitlines())
    binds = hop2_cap < 200
    assert bool(sorts) == binds and bool(scatters) == binds
    assert ("hop 2: cap 64 < 200 slots, ranked" in route[0]) == binds
    _, _, (_, counts) = step(state, m.sample(graph, roots))
    assert np.asarray(counts)[2] == 0


@pytest.mark.parametrize("walk_len", [5, 0], ids=["node2vec", "line"])
def test_lowered_shallow_step_holds_the_walk_scopes(graph, walk_len):
    """The shallow embedding models' step: the chained draws and the pair
    indexing under ``walk`` (LINE's one draw of a positive under
    ``draw``), the negatives' draw under ``negatives``, the gathers from
    the id-embedding tables and, transposed, the scatter-adds of their
    gradients under ``pair_rows``, the pair loss under ``loss``, Adam
    over the tables under ``optimizer``."""
    kw = dict(node_type=-1, edge_type=[0, 1], max_id=MAX_ID, dim=8,
              num_negs=3, xent_loss=True, device_sampling=True)
    m = Node2Vec(walk_len=walk_len, left_win_size=2, right_win_size=2,
                 **kw) if walk_len else LINE(order=2, **kw)
    opt = train_lib.get_optimizer("adam", 0.01)
    roots = graph.sample_node(8, -1)
    state = m.init_state(jax.random.PRNGKey(0), graph, roots, opt)
    text = jax.jit(m.make_train_step(opt)).lower(
        state, m.sample(graph, roots)).as_text(debug_info=True)
    here = {"negatives", "pair_rows", "loss", "optimizer",
            "walk" if walk_len else "draw"}
    for scope in TR.STEP_SCOPES:
        assert (f"/{scope}/" in text) == (scope in here), scope
    lines = text.splitlines()
    assert any("/pair_rows/" in ln and "gather" in ln for ln in lines)
    assert any("transpose(jvp(" in ln and "/pair_rows/" in ln
               and "scatter" in ln for ln in lines)
    assert not any("/pair_rows/" in ln and "/optimizer/" in ln
                   for ln in lines)


def test_lowered_attention_step_holds_the_edge_softmax_scope(graph):
    """``--aggregator attention`` on the same step: the softmax's max,
    exp and sums under ``edge_softmax`` (row reductions of the device
    expansion's regular list: no scatter there, forward or transposed),
    the projections and the gates under ``dense``."""
    m = _gcn_model(aggregator="attention")
    opt = train_lib.get_optimizer("adam", 0.01)
    roots = graph.sample_node(8, -1)
    state = m.init_state(jax.random.PRNGKey(0), graph, roots, opt)
    text = jax.jit(m.make_train_step(opt)).lower(
        state, m.sample(graph, roots)).as_text(debug_info=True)
    here = EXPAND_SCOPES | ATTENTION_SCOPES | {
        "gather_features", "gather_labels", "dense", "loss", "optimizer"}
    for scope in TR.STEP_SCOPES:
        assert (f"/{scope}/" in text) == (scope in here), scope
    lines = text.splitlines()
    for op in ("reduce_max", "exp", "reduce_sum"):
        assert any(f"/edge_softmax/{op}" in ln for ln in lines), op
    assert not any("/edge_softmax/" in ln and "scatter" in ln for ln in lines)
    assert any("transpose(jvp(" in ln and "/edge_softmax/" in ln
               for ln in lines)


def test_benchmark_keeps_the_same_scope_names():
    from benchmark import scopes

    assert scopes.STEP_SCOPES == TR.STEP_SCOPES
    assert scopes.STEP_HLO_FILE == TR.STEP_HLO_FILE


def test_profiled_run_leaves_the_compiled_step_text(graph, tmp_path):
    _train(graph, 6, profile_dir=str(tmp_path), profile_steps=(2, 4))
    text = (tmp_path / TR.STEP_HLO_FILE).read_text()
    assert text.startswith("HloModule jit_train_step")
    for scope in set(TR.STEP_SCOPES) - FAMILY_SCOPES:
        assert f"/{scope}/" in text, scope
    # the flag the text's compile is keyed with is put back
    assert not jax.config.jax_compilation_cache_include_metadata_in_key


# ---------------------------------------------------------------------------
# (b) host side: leaves tile the training thread, parents are their sums
# ---------------------------------------------------------------------------


def test_recorder_stop_takes_itself_out_and_leaves_another_in():
    """``stop()`` unregisters the recorder it is called on (two reads of
    a bound method are equal and never identical, so an ``is`` test never
    did), and only that one: a recorder stopped late must not take out
    the one that has started since."""
    first = TR.TraceRecorder().start()
    T.record_phase("fence", 5, step=1)
    first.stop()
    assert T._trace_sink is None
    T.record_phase("fence", 5, step=2)
    assert [e[3] for e in first.events()] == [1]
    second = TR.TraceRecorder().start()
    first.stop()
    assert T._trace_sink == second._on_phase
    T.record_phase("fence", 5, step=3)
    second.stop()
    assert [e[3] for e in second.events()] == [3]
    assert T._trace_sink is None


def _keyword_hook(seen, end_at):
    def hook(step, state=None, batch=None, loss=None):
        seen.append((step, sorted(state), sorted(batch), float(loss)))
        return step == end_at

    return hook


def _step_hook(seen, end_at):
    def hook(step):
        seen.append((step,))
        return step == end_at

    return hook


@pytest.mark.parametrize("make, end_at, seen_steps", [
    (_keyword_hook, 8, [1, 2, 3, 4, 8]),
    (_keyword_hook, None, [1, 2, 3, 4, 8, 12]),
    (_step_hook, 8, list(range(1, 9))),
    (lambda seen, _: lambda step: seen.append((step,)), None,
     list(range(1, 13))),
], ids=["keywords-ends", "keywords", "step_alone-ends", "lambda_step_none"])
def test_step_hook_is_fed_where_it_takes_keywords_and_may_end_the_loop(
        graph, make, end_at, seen_steps):
    """A hook that takes ``state=``, ``batch=`` and ``loss=`` is handed
    what each dispatch produced, its first three steps one dispatch
    each (a device-sampled chunk ends at each log window's end here);
    one that takes the step alone (run_loop's metrics emitter,
    ``lambda step: None``) is called for every step, as before; either
    ends the loop after that dispatch, through its ``finally``, by
    returning True: the journal's collector callback is gone, the last
    partial log window is flushed, and the state of the last step comes
    back."""
    import gc

    seen = []
    callbacks = list(gc.callbacks)
    state, history = _train(graph, 12, step_hook=make(seen, end_at))
    assert [s[0] for s in seen] == seen_steps
    assert gc.callbacks == callbacks
    steps = seen_steps[-1]
    # log_every 4: whole windows and the last partial one
    assert len(history) == -(-steps // 4)
    assert int(state["opt_state"][0].count) == steps    # Adam's own
    if make is _keyword_hook:
        for _, state_keys, batch_keys, loss in seen:
            assert "params" in state_keys and "opt_state" in state_keys
            assert batch_keys and np.isfinite(loss)


@pytest.mark.parametrize("device_sampling, firsts", [
    (False, range(24)),       # host-sampled: a step a dispatch
    # chunks: the hook's first three steps, then cut at each log
    # window's end
    (True, [0, 1, 2, 3, 4, 8, 12, 16, 20]),
], ids=["host_sampled", "device_sampled"])
def test_leaves_tile_every_step_and_parents_are_their_sums(
        graph, tmp_path, device_sampling, firsts):
    rec = TR.TraceRecorder().start()
    try:
        _train(graph, 24, step_hook=lambda step: None,
               checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=8,
               device_sampling=device_sampling)
    finally:
        rec.stop()
    main = _loop_events(rec)
    names = {e[0] for e in main}
    # parents never reach the sink from the training thread, nor `stall`
    assert not names & {"device", "host", "stall"}
    # (h2d is this thread's on the tests' virtual CPU mesh, where train()
    # takes the copy out of the prefetch workers)
    assert names == {"step", *TRAIN_THREAD_LEAVES}
    _assert_leaves_tile(main, firsts)

    h = T.phase_hists()
    # the leaves and parents are recorded once a dispatch
    for name in ("h2d", "dispatch", "fence", "hook", "host_other",
                 "input_other", "device", "host", "step"):
        assert h[name]["count"] == len(firsts), name
    # (a window is pulled after the next dispatch: the last one's, at 24,
    # after the loop)
    assert h["log_flush"]["count"] == 5 and h["checkpoint"]["count"] == 3
    # the steps of each dispatch: a value histogram beside the leaves
    assert h["dispatch_steps"]["count"] == len(firsts)
    assert h["dispatch_steps"]["sum_us"] == 24
    for parent in ("device", "host"):
        kids = sum(h[c]["sum_us"] for c, p in T.PHASE_PARENT.items()
                   if p == parent)
        # the leaves are cut from the parent's own clock readings
        assert kids == h[parent]["sum_us"], parent
    # and the whole: step = every leaf of this thread
    leaves = sum(h[n]["sum_us"] for n in TRAIN_THREAD_LEAVES)
    assert leaves == pytest.approx(h["step"]["sum_us"], rel=0.01)


def test_recorder_places_a_span_at_its_end_stamp():
    rec = TR.TraceRecorder().start()
    try:
        T.record_phase("fence", 250, step=3, end_us=1_000_000)
        T.record_phase_span("input_other", 10, 40, step=3)
        T.record_phase_span("input_other", 40, 40, step=3)  # empty: dropped
        T.record_phase_hist("device", 250)                  # no span
        before = TR.now_us()
        T.record_phase("sample", 100)                       # no stamp: now
    finally:
        rec.stop()
    evs = rec.events()
    assert evs[0][:4] == ("fence", 999_750, 250, 3)
    assert evs[1][:4] == ("input_other", 10, 30, 3)
    assert len(evs) == 3 and evs[2][0] == "sample"
    assert before - 100 <= evs[2][1] <= TR.now_us()
    h = T.phase_hists()
    assert h["device"]["count"] == 1 and h["input_other"]["count"] == 0


# ---------------------------------------------------------------------------
# (b2) when the thread waits for the device: a fence every sync_every-th
# step and one pull a log window, with the phases recorded or not
# ---------------------------------------------------------------------------

SYNC_STEPS = 70
SYNC_LOG_EVERY = 20
# the first steps of the device-sampled dispatches there: the hook's
# first three steps, then ten steps on or to a log window's end
CHUNK_FIRSTS = [0, 1, 2, 3, 13, 20, 30, 40, 50, 60]


def _train_sync(graph, devices=1, **kw):
    """Roots by the step number; the draws ride the batch's seed, a
    counter of the model: with the batches made in step order (inline,
    ``prefetch_threads=1``) a run repeats to the bit."""
    nodes = np.unique(graph.sample_node(256, -1))  # every node, in order
    # The flight recorder hands every thread that records a ring of its
    # fixed pool of 64 and never takes one back: with it on, each run's
    # prefetch workers would take two from every file that follows in
    # this process (tests/test_blackbox.py needs some left for its shard).
    recording = blackbox.blackbox_enabled()
    blackbox.set_blackbox(False)
    try:
        return _train(
            graph, SYNC_STEPS,
            source_fn=lambda step: np.random.default_rng(step).choice(
                nodes, 8),
            mesh=make_mesh(devices), log_every=SYNC_LOG_EVERY, **kw)
    finally:
        blackbox.set_blackbox(recording)


@pytest.mark.parametrize("devices, device_sampling, firsts, fenced", [
    # a real device, or one CPU device: run-ahead bounded at 32 steps,
    # fenced by the dispatch that passes a multiple of 32
    (1, False, range(SYNC_STEPS), [31, 63]),
    (1, True, CHUNK_FIRSTS, [30, 60]),
    # a virtual CPU mesh: a queued step can starve a collective's
    # rendezvous, so every dispatch is fenced
    (2, False, range(SYNC_STEPS), list(range(SYNC_STEPS))),
    (2, True, CHUNK_FIRSTS, CHUNK_FIRSTS),
], ids=["1-host_sampled", "1-device_sampled", "2-host_sampled",
        "2-device_sampled"])
def test_fence_spans_lie_on_the_sync_steps_and_leaves_tile(
        graph, devices, device_sampling, firsts, fenced):
    rec = TR.TraceRecorder().start()
    try:
        _train_sync(graph, devices, step_hook=lambda step: None,
                    phase_profile=True, device_sampling=device_sampling)
    finally:
        rec.stop()
    main = _loop_events(rec)
    assert sorted(e[3] for e in main if e[0] == "fence") == fenced
    assert {e[0] for e in main} <= {"step", *TRAIN_THREAD_LEAVES}
    _assert_leaves_tile(main, firsts)
    h = T.phase_hists()
    for name in ("dispatch", "device", "host", "step", "hook"):
        assert h[name]["count"] == len(firsts), name
    assert h["fence"]["count"] == len(fenced)
    assert h["log_flush"]["count"] == SYNC_STEPS // SYNC_LOG_EVERY
    # a parent is the sum of its leaves: of the same clock readings
    for parent in ("device", "host"):
        kids = sum(h[c]["sum_us"] for c, p in T.PHASE_PARENT.items()
                   if p == parent)
        assert kids == h[parent]["sum_us"], parent


def test_history_is_the_same_with_the_phases_recorded_or_not(graph):
    _, on = _train_sync(graph, prefetch_threads=1, phase_profile=True)
    _, off = _train_sync(graph, prefetch_threads=1, phase_profile=False)
    assert len(on) == len(off) == -(-SYNC_STEPS // SYNC_LOG_EVERY)
    for a, b in zip(on, off):
        assert a["loss"] == b["loss"] and a["f1"] == b["f1"], (a, b)
    assert len({h["loss"] for h in on}) > 1  # it trained


def test_flush_pulls_a_window_from_the_device_once(graph, monkeypatch):
    pulls, on_host, counted = [], [], []
    device_get = jax.device_get
    accumulate = train_lib._metric_accumulate

    def counting_get(tree):
        pulls.append(tree)
        return device_get(tree)

    def accumulate_host_values(name, acc, value):
        on_host.append(isinstance(value, np.ndarray))
        return accumulate(name, acc, value)

    monkeypatch.setattr(
        devprof, "count_d2h",
        lambda tree: counted.append(devprof.tree_bytes(tree)))
    monkeypatch.setattr(jax, "device_get", counting_get)
    monkeypatch.setattr(
        train_lib, "_metric_accumulate", accumulate_host_values)
    lines = []
    _, history = _train_sync(graph, log_fn=lines.append, phase_profile=True)
    windows = [SYNC_LOG_EVERY] * (SYNC_STEPS // SYNC_LOG_EVERY) + [
        SYNC_STEPS % SYNC_LOG_EVERY]
    # one pull a window: the window's metrics (a chunk's stacked rows a
    # dispatch, ten steps each here) and its last loss together
    assert [10 * len(metrics) for metrics, _loss in pulls] == windows
    assert all(isinstance(loss, jax.Array) for _metrics, loss in pulls)
    # nothing reaches the host-side accumulation as a device array
    assert len(on_host) == SYNC_STEPS and all(on_host)
    steps = [ln for ln in lines if ln.startswith("step=")]
    assert len(history) == len(steps) == len(windows)
    # the bytes counted are the window's, as before the pull was one
    assert counted == [
        sum(m.nbytes for m in metrics) + loss.nbytes
        for metrics, loss in pulls]


# ---------------------------------------------------------------------------
# (c) the kill-switch: nothing of this runs
# ---------------------------------------------------------------------------


def test_no_phase_is_recorded_with_phase_profile_off(graph, monkeypatch):
    calls = []
    real = native.lib().eg_phase_record
    monkeypatch.setattr(
        T, "record_phase", lambda *a, **k: calls.append(a))
    monkeypatch.setattr(
        T, "record_phase_hist", lambda *a, **k: calls.append(a))
    monkeypatch.setattr(
        T, "record_phase_span", lambda *a, **k: calls.append(a))
    T.set_trace_sink(lambda *a: calls.append(a))
    callbacks = list(gc.callbacks)
    _train(graph, 6, phase_profile=False, step_hook=lambda step: None)
    assert calls == []
    assert gc.callbacks == callbacks
    assert real is native.lib().eg_phase_record
    h = T.phase_hists()
    # (a compile listener that an earlier file of this worker armed
    # records its three phases whatever train() is told)
    listener = set(devprof.EVENT_PHASE.values())
    assert all(h[n]["count"] == 0 for n in T.PHASES if n not in listener), h


def test_telemetry_off_means_phase_profile_off(graph):
    T.set_telemetry(False)
    seen = []
    T.set_trace_sink(lambda *a: seen.append(a))
    _train(graph, 4)
    assert seen == []


# ---------------------------------------------------------------------------
# (d) the stall journal
# ---------------------------------------------------------------------------


def test_a_stalled_step_is_journalled_with_its_cause(graph):
    # No periodic job may be alive in this process, or its tick inside
    # the stalled step is journalled, and rightly: `run_loop.main()`
    # leaves the device-memory sampler ticking once a second, and which
    # files ran before this one in a worker is the scheduler's choice.
    # Both samplers end within a tick of theirs: long before step 30.
    devprof.stop_sampler()
    blackbox.stop_sampler()
    stamps, slept = [], []

    def hook(step):
        stamps.append(time.monotonic())
        if step == 30:
            # 80 ms, or more where a loaded host makes the toy steps so
            # slow that 80 ms would be under 5 x their median: the
            # journal's, which at step 30 is still that of its first
            # FIRST steps, or the run's so far, whichever is larger
            gaps = [b - a for a, b in zip(stamps, stamps[1:])]
            usual = max(statistics.median(gaps),
                        statistics.median(gaps[:T.StallJournal.FIRST]))
            slept.append(max(0.08, 8 * usual))
            time.sleep(slept[0])
            gc.collect(0)  # a young collection: listed, and cheap

    callbacks = list(gc.callbacks)
    # host-sampled: the journal reads a step a dispatch
    _train(graph, 40, step_hook=hook, log_every=100, device_sampling=False)
    assert gc.callbacks == callbacks  # the collector's callback is gone
    entries = T.stall_journal()
    # (a host loaded enough to stall another step by itself adds entries)
    (e,) = [x for x in entries if x["step"] == 29]  # 0-based, as the spans
    assert e["leaf"] == "hook"
    assert slept[0] * 1e6 <= e["leaf_us"] <= e["total_us"] < 4_000_000
    assert e["total_us"] > 5 * e["median_us"] > 0
    # the thread slept: over the stretch since the journal last read the
    # thread's clocks, the CPU time beyond the usual is far under the
    # excess, and it gave the CPU up
    assert 1 <= e["since_steps"] <= T.StallJournal.REFRESH
    on_cpu = e["cpu_us"] - e["usual_cpu_us"] * e["since_steps"]
    assert on_cpu < 0.5 * e["excess_us"], e
    assert e["vcsw"] >= 1
    assert any(gen == 0 and thread == "MainThread"
               for gen, _us, thread in e["gc"]), e["gc"]
    assert e["ticks"] == []
    assert e["excess_us"] == e["total_us"] - e["median_us"]
    # (the hook runs with the step's device work still under way, and
    # the fence after it finds that done: of the sleep, up to a usual
    # step is no excess)
    assert slept[0] * 1e6 - e["median_us"] <= e["excess_us"]
    # one sample of the excess per entry in the `stall` histogram
    h = T.phase_hists()["stall"]
    assert h["count"] == len(entries)
    assert h["sum_us"] == sum(x["excess_us"] for x in entries)


def test_stall_journal_names_the_jobs_that_ticked_inside():
    j = T.StallJournal()
    try:
        t = TR.now_us() - 1000 * T.StallJournal.FIRST  # eight past steps
        for k in range(T.StallJournal.FIRST):
            j.step(k, t, t + 1000, {"fence": 900, "host_other": 100})
            t += 1000
        T.job_tick("eg-devprof-sampler")
        T.job_tick("eg-devprof-sampler", end=True)
        ticks = T.job_ticks()
        assert ticks["eg-devprof-sampler"][0] >= t
        assert ticks["blackbox-sampler"] == (0, 0)
        T.job_tick("metrics_every")  # begun, not ended: still at work
        j.step(8, t, TR.now_us() + 60_000,
               {"fence": 60_500, "host_other": 100})
    finally:
        j.close()
    (e,) = T.stall_journal()
    assert e["leaf"] == "fence" and e["leaf_typical_us"] == 900
    assert e["ticks"] == ["eg-devprof-sampler", "metrics_every"]
    assert e["median_us"] == 1000


def test_a_stall_on_the_very_step_the_journal_rereads_its_clocks():
    """A journalled step restarts the stretch the thread's clocks are read
    over; where it is also a step on which the median is re-read, the
    stretch is empty and must not be divided by."""
    j = T.StallJournal()
    try:
        t = 1_000_000
        for k in range(T.StallJournal.REFRESH - 1):
            j.step(k, t, t + 1000, {"fence": 1000})
            t += 1000
        j.step(T.StallJournal.REFRESH - 1, t, t + 90_000, {"fence": 90_000})
        j.step(T.StallJournal.REFRESH, t + 90_000, t + 91_000,
               {"fence": 1000})
    finally:
        j.close()
    (e,) = T.stall_journal()
    assert e["step"] == T.StallJournal.REFRESH - 1 and e["leaf"] == "fence"
    assert e["since_steps"] == T.StallJournal.REFRESH - T.StallJournal.FIRST


def test_detail_span_is_escaped_in_the_dump():
    native.lib().eg_telemetry_record_detail_span(
        77, 0, b'{"kind":"x","s":"a\\"b\\\\c"}')
    (s,) = T.slow_spans()
    assert s["total_us"] == 77 and s["end_us"] > 0
    assert s["detail"] == {"kind": "x", "s": 'a"b\\c'}
    assert T.stall_journal() == []
