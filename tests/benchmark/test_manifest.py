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


# ---- what a configuration's file names: its reference, its costs ----

def _with_config(tmp_path, edit):
    """The committed manifest with its first configuration's file
    replaced by an edited copy (under a path of the benchmark's)."""
    m = copy.deepcopy(_load())
    with open(os.path.join(ROOT, m["configs"][0]["file"])) as f:
        cfg = json.load(f)
    edit(cfg)
    os.makedirs(tmp_path / "cfg")
    (tmp_path / "cfg" / "edited.json").write_text(json.dumps(cfg))
    (tmp_path / "cfg" / "short_reference.py").write_text(
        "from benchmark.sage_reference import train_steps  # noqa: F401\n"
        "def init_state(cfg, key, optimizer): ...\n")
    m["paths"] = m["paths"] + ["cfg"]
    m["configs"][0]["file"] = "cfg/edited.json"
    return _problems_of(tmp_path, m)


def _no_costs_key(cfg):
    del cfg["costs"]


def _costs_file_missing(cfg):
    cfg["costs"] = "benchmark/configs/no_such_costs.py"


def _reference_file_missing(cfg):
    cfg["reference"] = "benchmark/configs/no_such_reference.py"


def _costs_outside_paths(cfg):
    cfg["costs"] = "bench.py"


def _reference_lacks_functions(cfg):
    cfg["reference"] = "cfg/short_reference.py"


@pytest.mark.parametrize("edit,says", [
    (_no_costs_key, "costs file None not found"),
    (_costs_file_missing, "no_such_costs.py' not found"),
    (_reference_file_missing, "no_such_reference.py' not found"),
    (_costs_outside_paths, "costs file lies outside paths"),
    (_reference_lacks_functions, "lacks ['batch_rows', 'compared_state'"),
], ids=lambda x: x.__name__.lstrip("_") if callable(x) else None)
def test_configuration_whose_named_files_are_wrong_is_refused(
        tmp_path, edit, says):
    found = _with_config(tmp_path, edit)
    assert any(says in p for p in found), found


def test_configuration_with_both_files_passes(tmp_path):
    assert _with_config(tmp_path, lambda cfg: None) == []


def test_the_toy_store_family_names_whole_files():
    """The second family's reference and cost function bind every
    function the harness calls (read as text, as the manifest does)."""
    toy = os.path.join(ROOT, "tests", "benchmark", "toy")
    with open(os.path.join(toy, "toy_store.json")) as f:
        cfg = json.load(f)
    for key, functions in manifest.CONFIG_FILES.items():
        path = os.path.join(os.path.dirname(toy), cfg[key])
        assert set(functions) <= manifest.bound_names(path), key


def test_unbroken_copy_passes(tmp_path):
    assert _problems_of(tmp_path, _load()) == []
