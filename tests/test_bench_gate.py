"""Device-selection contracts: the platform is what the environment says
and nothing downgrades it. bench.py's plausibility gate and killable
per-config subprocess, the one compile-cache decision, chip_smoke.py's
refusal to run without a TPU, the Pallas backend checks that no longer
swallow errors, and the native build that rebuilds when forced or when
its objects came from another host. bench.py and chip_smoke.py are
scripts, not part of the package, so their contracts get tests here.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH_PY = os.path.join(_REPO, "bench.py")
_CHIP_SMOKE_PY = os.path.join(_REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", _BENCH_PY
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_implausible_rejects_unexecuted_timings(bench):
    # 2.3 us/step is an enqueue, not an execution
    assert bench._implausible(0.0023, 0.5)
    assert bench._implausible(0.0, 0.5)


def test_implausible_rejects_garbage_losses(bench):
    assert bench._implausible(1.0, float("nan"))
    assert bench._implausible(1.0, np.asarray([0.1, np.inf]))


def test_implausible_accepts_real_measurements(bench):
    # the empty-body scan floor (0.133 ms) and real step times pass
    assert bench._implausible(0.133, 0.5) is None
    assert bench._implausible(1.27, np.asarray([0.7])) is None
    assert bench._implausible(28.6, 0.69) is None  # an XLA-CPU step


def test_watchdog_emits_json_on_hang():
    """A config that outlives the total budget is killed and the parent
    still prints the driver-parseable failure line, exit code 2."""
    env = dict(os.environ, EULER_TPU_BENCH_DEADLINE="2", JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, _BENCH_PY, "--configs", "ppi"],
        capture_output=True, text=True, timeout=90, env=env,
        cwd=os.path.dirname(_BENCH_PY),
    )
    assert r.returncode == 2
    j = json.loads(r.stdout.strip().splitlines()[-1])
    assert "watchdog" in j["error"] and j["value"] == 0.0


# ---- no fallback that hides the device ----


def _code_lines(path):
    """Source lines with comments and docstring prose out of the way:
    only what executes can downgrade a platform."""
    import ast

    with open(path) as f:
        src = f.read()
    tree = ast.parse(src)
    docs = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.FunctionDef, ast.ClassDef)):
            body = node.body
            if body and isinstance(body[0], ast.Expr) and isinstance(
                body[0].value, ast.Constant
            ) and isinstance(body[0].value.value, str):
                docs.update(range(body[0].lineno, body[0].end_lineno + 1))
    return [
        ln.split("#", 1)[0]
        for i, ln in enumerate(src.splitlines(), 1) if i not in docs
    ]


@pytest.mark.parametrize(
    "script", ["bench.py", os.path.join("scripts", "batch_sweep.py")]
)
def test_launchers_have_no_path_to_cpu(script):
    """No probe, no --platform, no forced CPU backend, no x3 scaling:
    bench.py and the sweep run on what the environment says or fail."""
    code = "\n".join(_code_lines(os.path.join(_REPO, script)))
    for gone in ("force_cpu_devices", "--platform", "probe_" "backend",
                 "--probe", "_go_cpu", "tpu_error", "jax_platforms",
                 "JAX_PLATFORMS"):
        assert gone not in code, f"{script} still mentions {gone}"


def test_bench_parent_never_initializes_a_backend(tmp_path):
    """One process per chip: with a platform that cannot initialize,
    the child fails and says so, and the parent — which would have died
    the same way had it touched JAX — reports the failed config and
    exits 1."""
    env = dict(os.environ, JAX_PLATFORMS="no_such_platform",
               EULER_TPU_BENCH_BANK=str(tmp_path))
    r = subprocess.run(
        [sys.executable, _BENCH_PY, "--smoke"], capture_output=True,
        text=True, timeout=120, env=env, cwd=_REPO,
    )
    assert r.returncode == 1, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["value"] == 0.0 and "no_such_platform" in line["error"]


def test_unknown_device_kind_is_an_error(bench, monkeypatch):
    assert bench._chip_peaks("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(ValueError, match="unknown device_kind"):
        bench._chip_peaks("TPU v99 imaginary")
    # the two environment overrides are gone with the default
    monkeypatch.setenv("EULER_TPU_PEAK_TFLOPS", "1")
    monkeypatch.setenv("EULER_TPU_PEAK_HBM_GBPS", "1")
    assert bench._chip_peaks("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(ValueError):
        bench._chip_peaks("cpu")


def test_failed_config_fails_the_child_exit_code(bench, tmp_path):
    """A config that raises is banked as its failure line AND returned
    as a non-zero exit code — not an error string beside an exit 0."""
    bank = str(tmp_path / "x.json")
    assert bench._run_one("no_such_config", bank, None) == 1
    with open(bank) as f:
        r = json.load(f)
    assert r["value"] == 0.0 and "KeyError" in r["error"]


def test_chip_smoke_refuses_cpu(tmp_path):
    """JAX_PLATFORMS=cpu python chip_smoke.py: non-zero within seconds,
    naming the platform it found, no result line — also from a directory
    that holds chip_smoke.py and nothing else of the repo."""
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(_CHIP_SMOKE_PY, alone / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    for cwd in (_REPO, str(alone)):
        t0 = time.monotonic()
        r = subprocess.run(
            [sys.executable, "chip_smoke.py"], capture_output=True,
            text=True, timeout=120, env=env, cwd=cwd,
        )
        assert r.returncode != 0
        assert time.monotonic() - t0 < 60
        assert "platform=cpu" in r.stderr
        assert not any(
            ln.lstrip().startswith("{") for ln in r.stdout.splitlines()
        ), r.stdout
    assert not (alone / ".data").exists()  # nothing built before the check


# ---- the compile cache is placed from outside, in one place ----


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from euler_tpu.parallel import mesh

    seen = []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(mesh.jax.config, "update",
                        lambda k, v: seen.append((k, v)))
    monkeypatch.setattr(mesh.jax, "default_backend", lambda: "tpu")
    want = os.path.join(_REPO, ".jax_cache")
    assert mesh.enable_compile_cache() == want
    assert [kv for kv in seen if kv[0] == "jax_compilation_cache_dir"] == [
        ("jax_compilation_cache_dir", want)
    ]


def test_compile_cache_stays_off_on_cpu(monkeypatch):
    """XLA:CPU logs a machine-feature mismatch on every cache hit, so a
    CPU run keeps no cache unless the environment asks for one."""
    from euler_tpu.parallel import mesh

    seen = []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(mesh.jax.config, "update",
                        lambda k, v: seen.append(k))
    assert mesh.jax.default_backend() == "cpu"
    assert mesh.enable_compile_cache() is None
    assert seen == []


def test_compile_cache_env_var_stands(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own handling of it
    stands on every backend: no code sets jax_compilation_cache_dir."""
    from euler_tpu.parallel import mesh

    seen = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(mesh.jax.config, "update",
                        lambda k, v: seen.append(k))
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(mesh.jax, "default_backend", lambda b=backend: b)
        assert mesh.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in seen


def test_one_place_sets_the_cache_dir():
    """Exactly one module names the config key or builds a cache path;
    every entry point goes through it."""
    setters, users = [], []
    for root in ("euler_tpu", "scripts", "examples", "tests"):
        for d, _, files in os.walk(os.path.join(_REPO, root)):
            setters += [os.path.join(d, f) for f in files
                        if f.endswith((".py", ".sh"))]
    setters += [_BENCH_PY, _CHIP_SMOKE_PY]
    naming = []
    for path in setters:
        if path == os.path.abspath(__file__):
            continue
        with open(path) as f:
            src = f.read()
        if "jax_compilation_cache_dir" in src or "jax_cache" in src:
            naming.append(os.path.relpath(path, _REPO))
        if "enable_compile_cache()" in src:
            users.append(os.path.relpath(path, _REPO))
    assert naming == [os.path.join("euler_tpu", "parallel", "mesh.py")]
    for entry in ("euler_tpu/run_loop.py", "euler_tpu/serve.py", "bench.py",
                  "scripts/batch_sweep.py", "chip_smoke.py"):
        assert entry in users, entry


# ---- the Pallas backend checks say what they find ----


def test_backend_ok_lets_a_pallas_import_error_through_on_tpu(monkeypatch):
    import jax
    import jax.experimental

    from euler_tpu.graph import pallas_sampling as ps

    # any other backend: no kernel, and no import attempted
    monkeypatch.setitem(sys.modules, "jax.experimental.pallas", None)
    monkeypatch.delattr(jax.experimental, "pallas", raising=False)
    assert jax.default_backend() == "cpu"
    assert ps._backend_ok(require_single_device=False) is False
    # a TPU backend whose Pallas does not import is a broken
    # installation, not a quiet route to the XLA chain
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ImportError):
        ps._backend_ok(require_single_device=False)
    with pytest.raises(ImportError):
        ps.sharded_available()


def test_interpret_params_refuses_on_tpu(monkeypatch):
    import jax

    from euler_tpu.graph import pallas_sampling as ps

    monkeypatch.delenv("EULER_TPU_PALLAS_INTERPRET", raising=False)
    assert ps.interpret_params() is False
    monkeypatch.setenv("EULER_TPU_PALLAS_INTERPRET", "1")
    assert ps.interpret_params() is not False  # CPU: the emulator
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="on a TPU backend"):
        ps.interpret_params()


# ---- built on this host, from what git would commit ----


def test_forced_native_build_passes_make_B(monkeypatch):
    from euler_tpu.graph import native

    cmds = []
    monkeypatch.delenv("EG_NATIVE_LIB", raising=False)
    monkeypatch.setattr(
        native.subprocess, "run", lambda cmd, **kw: cmds.append(cmd)
    )
    native.build_native()
    native.build_native(force=True)
    assert "-B" not in cmds[0] and "-B" in cmds[1]


def _make_plan(native_dir, *flags):
    """Compile commands `make -n` would run in native_dir."""
    r = subprocess.run(
        ["make", "-n", *flags], cwd=native_dir, capture_output=True,
        text=True, check=True,
    )
    return [ln for ln in r.stdout.splitlines() if " -c " in ln]


def test_forced_native_build_really_rebuilds():
    """An up-to-date tree plans nothing; -B plans every object again
    (plain `make` after a copy sees fresh .o files and does nothing —
    which is why force must not mean plain make)."""
    from euler_tpu.graph import native

    native.lib()  # built and current
    sources = [f for f in os.listdir(native._NATIVE_DIR) if f.endswith(".cc")]
    assert _make_plan(native._NATIVE_DIR) == []
    assert len(_make_plan(native._NATIVE_DIR, "-B")) == len(sources)


def test_native_objects_from_another_host_are_rebuilt(tmp_path):
    """The tree is copied between machines with its ignored files: a
    build marker naming another host's CPU drops the objects and the
    library at parse time, so the next make rebuilds everything."""
    from euler_tpu.graph import native

    native.lib()
    copy = str(tmp_path / "_native")
    shutil.copytree(native._NATIVE_DIR, copy)
    sources = [f for f in os.listdir(copy) if f.endswith(".cc")]
    assert _make_plan(copy) == []  # same host: trusted
    with open(os.path.join(copy, ".flavor")) as f:
        flavor, host = f.read().split()
    with open(os.path.join(copy, ".flavor"), "w") as f:
        f.write(f"{flavor} {int(host) + 1}\n")
    assert len(_make_plan(copy)) == len(sources)
    assert not os.path.exists(os.path.join(copy, "libeuler_graph.so"))
    with open(os.path.join(copy, ".flavor")) as f:
        assert f.read().split() == [flavor, host]


def test_bank_write_atomic(bench, tmp_path):
    p = str(tmp_path / "x.json")
    bench._bank_write(p, {"a": 1})
    bench._bank_write(p, {"a": 2})
    assert json.load(open(p)) == {"a": 2}
    assert not os.path.exists(p + ".tmp")


def test_spawn_config_banks_child_failure_as_final(bench, tmp_path):
    """The child process banks even its failure line (marked final), so
    the parent distinguishes 'config failed' from 'child hung before
    banking anything'."""
    r, timed_out = bench._spawn_config(
        "no_such_config", 120.0, str(tmp_path), None
    )
    assert r is not None and not timed_out
    assert r["value"] == 0.0 and "KeyError" in r["error"]
    assert r["detail"]["banked"] == "final"


def test_spawn_config_kills_hung_child(bench, tmp_path):
    """A child that banks nothing within its deadline is SIGKILLed and
    reported as None — one hung config cannot eat the others' time."""
    t0 = time.monotonic()
    r, timed_out = bench._spawn_config("ppi", 3.0, str(tmp_path), None)
    dt = time.monotonic() - t0
    assert r is None and timed_out
    assert dt < 30, f"kill took {dt:.0f}s"


def test_heavytail_config_has_no_shape_literals(bench):
    """The reddit_heavytail graph shape comes from
    datasets.REDDIT_HEAVYTAIL at run time (run_config merges it in); a
    shape literal re-appearing in CONFIGS would shadow the authoritative
    constant, silently invalidate the shared ~2 GB cache, and measure a
    different graph than PERF.md describes."""
    from euler_tpu.datasets import REDDIT_HEAVYTAIL

    cfg = bench.CONFIGS["reddit_heavytail"]
    assert cfg.get("powerlaw") and cfg.get("alias_sampling")
    overlap = set(cfg) & set(REDDIT_HEAVYTAIL)
    assert not overlap, f"shape keys must live in datasets only: {overlap}"
    # and the merge supplies everything run_config's build needs
    merged = {**cfg, **REDDIT_HEAVYTAIL}
    for key in ("num_nodes", "num_edges", "feature_dim", "label_dim",
                "alpha", "multilabel", "batch", "fanouts", "dim", "lr",
                "warmup", "measure"):
        assert key in merged, key


def test_default_configs_gated_on_heavytail_cache(bench, tmp_path,
                                                  monkeypatch):
    """The no-flag config list includes the 113.7M-edge flagship ONLY
    when its cache is finished with current params — an absent cache
    must never trigger an implicit multi-minute rebuild mid-window."""
    monkeypatch.setenv("EULER_TPU_HEAVYTAIL_CACHE", str(tmp_path / "no"))
    assert bench.default_configs() == "reddit,ppi"

    from euler_tpu.datasets import (
        REDDIT_HEAVYTAIL, heavytail_cache_dir, powerlaw_cache_ready,
    )

    real = os.path.join(os.path.dirname(_BENCH_PY), ".data", "reddit_ht")
    monkeypatch.setenv("EULER_TPU_HEAVYTAIL_CACHE", real)
    if powerlaw_cache_ready(heavytail_cache_dir(), **REDDIT_HEAVYTAIL):
        assert bench.default_configs() == "reddit_heavytail,reddit,ppi"
    else:
        assert bench.default_configs() == "reddit,ppi"
