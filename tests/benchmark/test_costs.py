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
