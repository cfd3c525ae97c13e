"""The FLOPs and bytes functions against numbers worked by hand from the
two recipes' shapes."""

import json
import os

import pytest

from benchmark import costs, peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_reddit_costs_by_hand():
    c = costs.step_costs(_cfg("graphsage_reddit"), 1000, True)
    # rows: 1000 roots, 4000 hop-1, 16000 hop-2; half width 32
    fwd0 = 2 * 5000 * 602 * 32 * 2      # 385,280,000
    fwd1 = 2 * 1000 * 64 * 32 * 2       # 8,192,000
    out = 2 * 1000 * 64 * 41            # 5,248,000
    assert c["flops"] == 2 * fwd0 + 3 * fwd1 + 3 * out == 810_880_000
    assert c["gather_bytes"] == 21000 * 602 * 4 + 1000 * 41 * 4 == 50_732_000
    assert c["draw_bytes"] == 5000 * 60 * 8 + 20000 * 4 == 2_480_000
    params = 2 * 602 * 32 + 2 * 64 * 32 + 64 * 41 + 41
    assert c["params"] == params == 45_289
    assert c["bytes"] == 50_732_000 + 2_480_000 + 7 * 4 * params
    assert c["edges"] == 20_000


def test_ppi_costs_by_hand():
    c = costs.step_costs(_cfg("graphsage_ppi"), 512, True)
    fwd0 = 2 * (512 + 5120) * 50 * 128 * 2   # 144,179,200
    fwd1 = 2 * 512 * 256 * 128 * 2           # 67,108,864
    out = 2 * 512 * 256 * 121                # 31,719,424
    assert c["flops"] == 2 * fwd0 + 3 * fwd1 + 3 * out == 584_843_264
    assert c["gather_bytes"] == 56832 * 50 * 4 + 512 * 121 * 4 == 11_614_208
    assert c["draw_bytes"] == 5632 * 60 * 8 + 56320 * 4 == 2_928_640
    assert c["edges"] == 56_320


# what benchmark/costs.py itself computed before the cost function moved
# to the configurations (commit fb08bce), digit for digit
PARENT = {
    ("graphsage_reddit", 1000, True): {
        "flops": 810880000.0, "bytes": 54480092.0,
        "gather_bytes": 50732000.0, "draw_bytes": 2480000.0,
        "opt_bytes": 1268092.0, "params": 45289, "edges": 20000},
    ("graphsage_reddit", 1000, False): {
        "flops": 810880000.0, "bytes": 52084092.0,
        "gather_bytes": 50732000.0, "draw_bytes": 0.0,
        "opt_bytes": 1268092.0, "params": 45289, "edges": 20000},
    ("graphsage_ppi", 512, True): {
        "flops": 584843264.0, "bytes": 17606972.0,
        "gather_bytes": 11614208.0, "draw_bytes": 2928640.0,
        "opt_bytes": 3064124.0, "params": 109433, "edges": 56320},
    ("graphsage_ppi", 512, False): {
        "flops": 584843264.0, "bytes": 14905660.0,
        "gather_bytes": 11614208.0, "draw_bytes": 0.0,
        "opt_bytes": 3064124.0, "params": 109433, "edges": 56320},
}


@pytest.mark.parametrize("case", sorted(PARENT), ids=lambda c: "%s-%d-%s" % c)
def test_costs_are_the_parents_to_the_last_digit(case):
    name, batch, device_sampling = case
    assert costs.step_costs(_cfg(name), batch, device_sampling) == PARENT[case]


def test_toy_store_costs_by_hand():
    """The second family's cost function, found through its
    configuration: one drawn hop, so 4 edges a root where ``fanouts``
    reads "4,4"."""
    toy = os.path.join(ROOT, "tests", "benchmark")
    with open(os.path.join(toy, "toy", "toy_store.json")) as f:
        cfg = json.load(f)
    c = costs.step_costs(cfg, 32, True, root=toy)
    assert c["edges"] == 32 * 4 == 128
    fwd0 = 2 * 32 * 20 * 8 * 2          # roots only, two branches
    fwd1 = 2 * 32 * 16 * 8 * 2
    out = 2 * 32 * 16 * 41
    assert c["flops"] == 3 * (fwd0 + fwd1 + out) == 236_544
    assert c["gather_bytes"] == 160 * 20 * 4 + 32 * 41 * 4
    assert c["store_bytes"] == (3 * 128 + 3 * 32) * 16 * 4
    assert c["draw_bytes"] == 32 * 12 * 8 + 128 * 4
    params = 2 * 20 * 8 + 2 * 16 * 8 + 16 * 41 + 41
    assert c["opt_bytes"] == 2 * 7 * 4 * params
    assert c["bytes"] == (c["gather_bytes"] + c["store_bytes"]
                          + c["draw_bytes"] + c["opt_bytes"])


def test_a_cost_function_that_leaves_a_key_out_is_refused(tmp_path):
    (tmp_path / "short.py").write_text(
        "def step_costs(cfg, b, ds):\n"
        "    return {'edges': 1, 'flops': 1.0, 'bytes': 1.0}\n")
    with pytest.raises(ValueError, match="draw_bytes"):
        costs.step_costs({"costs": "short.py"}, 8, True, root=str(tmp_path))


def test_host_sampled_counts_ids_not_draws():
    c = costs.step_costs(_cfg("graphsage_reddit"), 1000, False)
    assert c["draw_bytes"] == 0
    assert c["bytes"] == 50_732_000 + 21000 * 4 + 7 * 4 * 45_289


def test_reddit_step_is_memory_bound_on_v5e():
    c = costs.step_costs(_cfg("graphsage_reddit"), 1000, True)
    p = peaks.chip_peaks("TPU v5 lite")
    assert c["bytes"] / p["hbm_bytes_per_s"] > c["flops"] / p["flops_per_s"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.chip_peaks("TPU v99")
