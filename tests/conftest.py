"""Test harness config: force JAX onto a virtual 8-device CPU mesh so every
sharding test runs without TPU hardware (JAX_PLATFORMS=cpu,
XLA_FLAGS=--xla_force_host_platform_device_count=8; Pallas kernels run
there through interpret mode, EULER_TPU_PALLAS_INTERPRET=1, see
tests/test_pallas_interpret.py)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from euler_tpu.parallel import enable_compile_cache, force_cpu_devices

# EULER_TPU_TESTS_ON_TPU=1 keeps the real backend so the TPU-only suites
# can run on a chip (compiles kept in the one compile cache every entry
# point uses); everything else in the suite still passes there but much
# slower, so target the run:
#   EULER_TPU_TESTS_ON_TPU=1 python -m pytest tests/test_pallas_sampling.py \
#       tests/test_alias_sampling.py tests/test_alias_walk.py
if os.environ.get("EULER_TPU_TESTS_ON_TPU") == "1":
    enable_compile_cache()
else:
    force_cpu_devices(8)

import pytest

from tests.fixture_graph import FIXTURE_META, fixture_nodes, write_fixture


@pytest.fixture(scope="session")
def fixture_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("graph")
    write_fixture(str(d), num_partitions=2)
    return str(d)


@pytest.fixture(scope="session")
def graph(fixture_dir):
    import euler_tpu

    return euler_tpu.Graph(directory=fixture_dir)


@pytest.fixture(scope="session")
def meta():
    return dict(FIXTURE_META)


@pytest.fixture(scope="session")
def nodes():
    return fixture_nodes()


def run_worker_processes(worker_src: str, per_proc_args, timeout=300):
    """Launch one python subprocess per args tuple running ``worker_src``
    and return each one's stdout. Shared by the multi-process distributed
    tests. Guarantees sibling cleanup: if any worker fails or times out,
    the rest are killed (a surviving worker would otherwise sit blocked
    in a jax.distributed collective holding its ports). Asserts rc==0
    with the worker's stderr tail as the message."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(__file__))
    env.pop("XLA_FLAGS", None)  # workers set their own device count
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", worker_src, *map(str, args)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        for args in per_proc_args
    ]
    outs = []
    try:
        for pid, p in enumerate(procs):
            out, err = p.communicate(timeout=timeout)
            if (
                p.returncode != 0
                and "Multiprocess computations aren't implemented" in err
            ):
                # this jaxlib's CPU backend has no cross-process
                # collective support — an environment limit, not a
                # regression in the code under test (the same recipe
                # passes on backends that implement them)
                import pytest

                pytest.skip(
                    "CPU backend lacks multiprocess computations "
                    "(jax.distributed collectives unavailable)"
                )
            assert p.returncode == 0, f"worker {pid} failed:\n{err[-2500:]}"
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return outs


def free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port
