"""What the compiled train step needs beside its arguments and results,
in GB (1e9 bytes): ``memory_analysis().temp_size_in_bytes`` of the step
``train()`` compiles in a profiled run, which the program keeps as the
gauge ``step_temp_bytes`` of its ledger's resource section.
``device.peak_hbm_gb`` reads ``memory_stats()``, which does not count a
program's temporaries: a step that holds a multi-GB intermediate shows
here alone. 0 is a reading (a step with no temporary to speak of: a
traced run always sets the gauge); silent on a program that keeps no
such gauge."""

import sys


def read(ctx):
    # the program's own ledger, where the harness has the program loaded
    telemetry = sys.modules.get("euler_tpu.telemetry")
    if telemetry is None:
        return None
    resource = telemetry.telemetry_json().get("resource", {})
    if "step_temp_bytes" not in resource:
        return None
    return resource["step_temp_bytes"] / 1e9
