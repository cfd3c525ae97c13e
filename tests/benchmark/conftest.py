"""Every test file leaves the program's compile listener as it found it.

A benchmark run arms the program's device plane (``devprof.setup``), and
the ``jax.monitoring`` listener it registers outlives the run. A
benchmark run is a process of its own; in a test worker the next file
would record ``compile`` spans it does not expect. That is true of this
directory's files (``test_harness_cpu.py``) and of the program's own:
``tests/test_devprof.py`` arms the listener and never takes it out, and
``tests/test_step_tracing.py``, where it follows in the same worker,
fails three tests (``pytest tests/test_devprof.py
tests/test_step_tracing.py`` shows it, at any commit). Which files share
a worker hangs on how long every file takes, the benchmark's among them,
and with PR 29's the two mostly met (PERF.md section 7). So the rule is
kept here for every file of a run that collects this directory, once,
and not by a fixture in each file that arms the plane: when a file's
first test begins, a listener that the file before it installed is
taken out again.
"""

import pytest

_before = {"file": None, "installed": False}


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_protocol(item):
    if item.path == _before["file"]:
        return
    import jax.monitoring

    from euler_tpu import devprof

    if devprof._installed and not _before["installed"]:
        jax.monitoring.unregister_event_duration_listener(
            devprof._on_event_duration)
        devprof._installed = False
    _before.update(file=item.path, installed=devprof._installed)
