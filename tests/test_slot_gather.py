"""Layer 0's blocked message gather (models/gcn.py ``_slot_rows``): where
a hop's parents are a previous hop's padded set, the stored-table rows of
its slots are gathered a block of parent rows at a time, and only the
blocks that hold a real parent row are read. The slots after those keep
the default row, which is what the one-pass gather reads there: the
messages, and so the training, are the one-pass route's to the bit."""

import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from euler_tpu import trace as TR
from euler_tpu import train as train_lib
from euler_tpu.graph import device as device_graph
from euler_tpu.models import SupervisedGCN
from euler_tpu.models import gcn as gcn_models

MAX_ID = 16  # fixture ids go up to 16
HOP1_CAP = 40  # 8 roots x the fixture's 5-wide slab: hop 2 has 40 parents


def _module(feature_dim):
    return gcn_models._SupervisedGCNModule(
        num_layers=2, dim=4, num_classes=2, feature_dim=feature_dim)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("C", [12, 16])
@pytest.mark.parametrize("real", [1, 3, 4, 5, "C"], ids=[
    "one_row", "block_less_one", "one_block", "block_plus_one", "all_rows"])
def test_blocked_gather_equals_one_pass_to_the_bit(monkeypatch, dtype, C,
                                                    real):
    """Parent rows of 3 slots, at most 5 rows a block: blocks of 4, the
    largest divisor of 12 or 16 parent rows that fits. The whole ``[C*W,
    lanes]`` result is ``table[ids]``, in either table dtype, and the
    slots read are the blocks that hold a real row."""
    W, lanes, default = 3, 8, 17
    real = C if real == "C" else real
    monkeypatch.setattr(gcn_models, "SLOT_BLOCK", 5 * W)
    rng = np.random.RandomState(real)
    table = jnp.asarray(rng.randn(default + 1, lanes), dtype=dtype)
    ids = np.full((C, W), default, np.int32)
    ids[:real] = rng.randint(0, default, (real, W))
    ids[:real, -1] = default  # a real row's masked tail
    ids = jnp.asarray(ids.reshape(-1))
    parents = {"nodes": jnp.zeros(C, jnp.int32), "real": jnp.int32(real)}
    mod = _module(lanes)
    rows, read = jax.jit(
        lambda i, t, p: mod._slot_rows(2, i, t, p))(ids, table, parents)
    np.testing.assert_array_equal(
        np.asarray(rows.rows), np.asarray(table[ids].astype(jnp.float32)))
    assert int(read) == -(-real // 4) * 4 * W


def test_hop_one_and_a_set_of_one_block_take_one_pass(monkeypatch):
    """The roots' hop, and a set whose rows all fit in one block, keep
    the single gather over every slot."""
    C, W = 4, 3
    monkeypatch.setattr(gcn_models, "SLOT_BLOCK", C * W)
    table = jnp.arange(24, dtype=jnp.float32).reshape(6, 4)
    ids = jnp.asarray(np.arange(C * W) % 6, dtype=jnp.int32)
    parents = {"nodes": jnp.zeros(C, jnp.int32), "real": jnp.int32(1)}
    for p in (None, parents):
        text = jax.jit(lambda i, t: _module(4)._slot_rows(1, i, t, p)).lower(
            ids, table).as_text()
        assert "while" not in text
        rows, read = _module(4)._slot_rows(1, ids, table, p)
        np.testing.assert_array_equal(rows.rows, table[ids])
        assert int(read) == C * W


def _gcn(aggregator="mean"):
    return SupervisedGCN(
        label_idx=2, label_dim=3, metapath=[[0, 1], [0, 1]], dim=16,
        max_nodes_per_hop=[HOP1_CAP, 200], max_edges_per_hop=[64, 256],
        aggregator=aggregator, feature_idx=0, feature_dim=2, max_id=MAX_ID,
        device_features=True, device_sampling=True,
    )


def _state(m, graph, roots):
    opt = train_lib.get_optimizer("adam", 0.01)
    return opt, m.init_state(jax.random.PRNGKey(0), graph, roots, opt)


def _three_steps(graph, aggregator, batches):
    m = _gcn(aggregator)
    opt, state = _state(m, graph, batches[0][0])
    step = jax.jit(m.make_train_step(opt))
    counts = []
    for _, batch in batches:
        state, _, (_, c) = step(state, batch)
        counts.append(np.asarray(c))
    return state["params"], counts


@pytest.mark.parametrize("aggregator", ["mean", "attention"])
def test_blocked_step_trains_as_the_one_pass_step(
        graph, monkeypatch, aggregator):
    """Three steps of the toy step in blocks of 2 parent rows (20 blocks
    of hop 1's 40-row set, several of them real) leave the parameters
    the one-pass route leaves, bit for bit."""
    m = _gcn(aggregator)
    batches = []
    for _ in range(3):
        roots = np.asarray(graph.sample_node(8, -1))
        batches.append((roots, m.sample(graph, roots)))
    monkeypatch.setattr(gcn_models, "SLOT_BLOCK", 10 ** 9)
    want, one_pass = _three_steps(graph, aggregator, batches)
    monkeypatch.setattr(gcn_models, "SLOT_BLOCK", 2 * 5)
    got, blocked = _three_steps(graph, aggregator, batches)
    # the blocked route read fewer of hop 2's rows, in more than a block
    assert all(b[3] < o[3] and b[3] - 8 * 5 >= 2 * 10
               for b, o in zip(blocked, one_pass))
    leaves = jax.tree_util.tree_leaves_with_path(got)
    assert len(leaves) >= 4
    for (path, g), w in zip(leaves, jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      str(path))


def test_step_counts_the_slots_its_messages_read(graph, monkeypatch):
    """``expand_gathered_slots`` (the step's fourth count) is hop 1's
    slots, all read, and hop 2's blocks that hold a real parent row,
    ``B * W`` slots each; the first three counts are the one-pass
    route's."""
    m = _gcn()
    roots = np.asarray(graph.sample_node(8, -1))
    opt, state = _state(m, graph, roots)
    adjs = [state["consts"]["adj"][k] for k in m._hop_adj_keys]
    W = adjs[0]["nbr"].shape[1]
    hops = device_graph.multi_hop_neighbor(adjs, roots, m.max_nodes_per_hop)
    real = int(hops[0]["real"])
    default = adjs[0]["nbr"].shape[0] - 1
    assert real == int(np.sum(np.asarray(hops[0]["nodes"]) != default)) > 2
    counts = {}
    for block_rows in (4, HOP1_CAP):
        monkeypatch.setattr(gcn_models, "SLOT_BLOCK", block_rows * W)
        step = jax.jit(m.make_train_step(opt))
        _, _, (_, c) = step(state, m.sample(graph, roots))
        counts[block_rows] = np.asarray(c)
    slots, edges, overflow, gathered = counts[4]
    assert slots == len(roots) * W + HOP1_CAP * W and overflow == 0
    assert gathered == len(roots) * W + -(-real // 4) * 4 * W
    assert counts[HOP1_CAP][3] == slots  # one block: every slot read
    np.testing.assert_array_equal(counts[4][:3], counts[HOP1_CAP][:3])


def _computations(hlo):
    """name -> the instruction lines of each computation of an HLO
    module's text."""
    comps, name = {}, None
    for ln in hlo.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", ln)
        if head:
            name = head.group(1)
            comps[name] = []
        elif ln.strip() == "}":
            name = None
        elif name is not None:
            comps[name].append(ln)
    return comps


def _reached(comps, root):
    """The lines of ``root`` and of every computation it calls."""
    seen, todo, lines = set(), [root], []
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        lines += comps[c]
        for ln in comps[c]:
            todo += re.findall(r"(?:calls|to_apply|body|condition)=%?"
                               r"([\w.\-]+)", ln)
    return lines


def test_compiled_blocked_step_scopes_its_loop_body(graph, monkeypatch,
                                                    caplog):
    """The blocked gather is a ``while`` whose body's gathers carry
    ``/gather_features/``; the ``while`` itself carries no declared
    scope (a capture's event of it would count its body's time again
    under that scope). The route log says the blocks once."""
    m = _gcn()
    roots = np.asarray(graph.sample_node(8, -1))
    opt, state = _state(m, graph, roots)
    monkeypatch.setattr(gcn_models, "SLOT_BLOCK", 4 * 5)
    gcn_models._log_slot_gather.cache_clear()
    with caplog.at_level(logging.INFO, logger="euler_tpu"):
        hlo = jax.jit(m.make_train_step(opt)).lower(
            state, m.sample(graph, roots)).compile().as_text()
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("slot gather:")]
    assert said == ["slot gather: hop 2 200 slots -> 10 blocks of 4 rows, "
                    "while the set's real rows last"]
    comps = _computations(hlo)
    loops = [ln for c in comps.values() for ln in c if " while(" in ln]
    assert len(loops) == 1
    op_name = re.search(r'op_name="([^"]*)"', loops[0]).group(1)
    assert not any(f"/{s}/" in op_name + "/" for s in TR.STEP_SCOPES)
    body = re.search(r"body=%?([\w.\-]+)", loops[0]).group(1)
    gathers = [ln for ln in _reached(comps, body) if " gather(" in ln]
    assert gathers and all("/gather_features/" in ln for ln in gathers)
