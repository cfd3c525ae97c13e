"""Device-selection contracts: the platform is what the environment says
and nothing downgrades it. The launchers that start a chip job have no
path to the CPU, chip_smoke.py refuses to run without a TPU, one place
decides the compile cache, the Pallas backend checks do not swallow
errors, and the native build rebuilds when forced or when its objects
came from another host, one make at a time, never rewriting a library a
process has loaded. chip_smoke.py and scripts/ are not part of the
package, so their contracts get tests here.
"""

import os
import shutil
import subprocess
import sys
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHIP_SMOKE_PY = os.path.join(_REPO, "chip_smoke.py")


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
    "script",
    ["chip_smoke.py", os.path.join("scripts", "probe_layout_cache.py")],
)
def test_launchers_have_no_path_to_cpu(script):
    """No probe, no --platform, no forced CPU backend: the launchers
    that start a chip job run on what the environment says or fail."""
    code = "\n".join(_code_lines(os.path.join(_REPO, script)))
    for gone in ("force_cpu_devices", "--platform", "probe_" "backend",
                 "--probe", "_go_cpu", "tpu_error", "jax_platforms",
                 "JAX_PLATFORMS"):
        assert gone not in code, f"{script} still mentions {gone}"


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
    setters.append(_CHIP_SMOKE_PY)
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
    for entry in ("euler_tpu/run_loop.py", "euler_tpu/serve.py",
                  "chip_smoke.py"):
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


def _native_copy(tmp_path, touch):
    """A built copy of the native tree whose ``touch`` source is newer
    than its object: the next make compiles it and links again."""
    from euler_tpu.graph import native

    native.lib()
    copy = str(tmp_path / "_native")
    shutil.copytree(native._NATIVE_DIR, copy)
    later = time.time() + 5
    os.utime(os.path.join(copy, touch), (later, later))
    return copy


def test_a_relink_leaves_the_loaded_library_in_place(tmp_path):
    """ld writes its output in place: a process that has the library
    mapped would run code that changes under it. The link goes to
    another name and is renamed over the library, so the file a process
    loaded is never written again."""
    copy = _native_copy(tmp_path, "eg_capi.cc")
    so = os.path.join(copy, "libeuler_graph.so")
    before = os.stat(so)
    subprocess.run(["make", "-s", "-j"], cwd=copy, check=True,
                   capture_output=True)
    after = os.stat(so)
    assert after.st_mtime > before.st_mtime  # it was linked again
    assert after.st_ino != before.st_ino
    assert not os.path.exists(os.path.join(copy, "tmp-libeuler_graph.so"))


def test_processes_that_build_at_once_all_load_the_library(tmp_path):
    """Six processes that import the package together over a stale tree
    (test workers, a benchmark's phases): each brings the library up to
    date and loads it. One make at a time; without the lock a second
    make relinked the library while another process dlopen()ed it
    ("file too short") or ran it."""
    copy = _native_copy(tmp_path, "eg_capi.cc")
    child = (
        "import ctypes, os, sys\n"
        "from euler_tpu.graph import native\n"
        "native._NATIVE_DIR = sys.argv[1]\n"
        "native._LIB_PATH = os.path.join(sys.argv[1], 'libeuler_graph.so')\n"
        "L = ctypes.CDLL(native.build_native())\n"
        "assert L.eg_stat_count() > 0\n"
    )
    env = dict(os.environ, PYTHONPATH=_REPO)
    env.pop("EG_NATIVE_LIB", None)
    procs = [
        subprocess.Popen([sys.executable, "-c", child, copy], env=env,
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True)
        for _ in range(6)
    ]
    errors = []
    for p in procs:
        _, err = p.communicate(timeout=300)
        if p.returncode:
            errors.append(err.strip().splitlines()[-1:])
    assert errors == []
