"""The manifest check on the committed BENCHMARK.json, and on manifests
broken in the ways that refused earlier PRs."""

import copy
import json
import os

import pytest

from benchmark import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PATH = os.path.join(ROOT, "BENCHMARK.json")


def _load():
    with open(PATH) as f:
        return json.load(f)


def _problems_of(tmp_path, m):
    # a broken copy beside links to the real tree, so files still resolve
    for name in ("benchmark", "tests", "bench.py"):
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    p = tmp_path / "BENCHMARK.json"
    p.write_text(json.dumps(m))
    return manifest.problems(str(p))


def test_committed_manifest_passes():
    assert manifest.problems(PATH) == []


def test_run_seconds_limit_is_the_contracts():
    assert manifest.MAX_RUN_SECONDS == 51


def _layer_with_space(m):
    m["per_layer"][0]["layer"] = "input pipeline"


def _unit_with_space(m):
    m["end_to_end"][0]["unit"] = "edges per s"


def _greek_unit(m):
    m["per_layer"][0]["unit"] = "µs"


def _bound_too_wide(m):
    m["end_to_end"][0]["bound"] = 0.2


def _no_setup(m):
    m["end_to_end"] = [x for x in m["end_to_end"] if x["name"] != "setup_s"]


def _two_four_chip_cells(m):
    m["workloads"][0]["chips"] = 4


def _missing_reader(m):
    m["per_layer"][0]["name"] = "trainer.no_such_metric"


def _extra_key(m):
    m["per_layer"][0]["why"] = "because"


def _moves_unknown(m):
    m["per_layer"][0]["moves"] = "tokens_per_s"


def _no_workloads_list(m):
    del m["per_layer"][0]["workloads"]


def _duplicate_pair(m):
    m["workloads"][1]["config"] = m["workloads"][0]["config"]
    m["workloads"][1]["traffic"] = m["workloads"][0]["traffic"]


def _reduced_width(m):
    m["configs"][0]["reduced"] = ["feature_dim"]


def _unknown_traffic(m):
    m["workloads"][0]["traffic"] = "no_such_mix"


def _long_why(m):
    m["workloads"][0]["why"] = "x" * 201


def _run_seconds_too_long(m):
    m["run_seconds"] = 52


def _command_outside_paths(m):
    m["command"] = ["python3", "bench.py"]


def _unused_config(m):
    m["workloads"] = [w for w in m["workloads"]
                      if w["config"] != "graphsage_ppi"]
    for x in m["per_layer"]:
        x["workloads"] = [w for w in x["workloads"]
                          if w != "ppi_device_train"]


@pytest.mark.parametrize("breaker", [
    _layer_with_space, _unit_with_space, _greek_unit, _bound_too_wide,
    _no_setup, _two_four_chip_cells, _missing_reader, _extra_key,
    _moves_unknown, _no_workloads_list, _duplicate_pair, _reduced_width,
    _unknown_traffic, _long_why, _run_seconds_too_long,
    _command_outside_paths, _unused_config,
], ids=lambda f: f.__name__.lstrip("_"))
def test_broken_manifest_is_refused(tmp_path, breaker):
    m = copy.deepcopy(_load())
    breaker(m)
    assert _problems_of(tmp_path, m) != []


def test_unbroken_copy_passes(tmp_path):
    assert _problems_of(tmp_path, _load()) == []
