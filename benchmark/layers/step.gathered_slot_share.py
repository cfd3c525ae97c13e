"""The share of the full-neighbourhood expansion's padded slots whose
stored-table rows layer 0's messages read: the program's counter
``expand_gathered_slots`` (counted inside the jitted step: every slot of
a hop that takes one pass, and of an outer hop only the blocks of parent
rows that hold a real node) over ``expand_slots``, both as the process
has counted them by the end of the window. Lower means more of the
default parent rows' reads were skipped. Silent on a program that has no
such counter, or that expanded nothing."""

import sys


def read(ctx):
    # the program's own ledger, where the harness has the program loaded
    telemetry = sys.modules.get("euler_tpu.telemetry")
    if telemetry is None:
        return None
    counters = telemetry.telemetry_json().get("counters", {})
    slots = counters.get("expand_slots", 0)
    if not slots or "expand_gathered_slots" not in counters:
        return None
    return 100.0 * counters["expand_gathered_slots"] / slots
