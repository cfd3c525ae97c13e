"""The share of the full-neighbourhood expansion's padded slots that
carry a true edge: the program's counter ``expand_edges`` (the sum of the
hops' masks, counted inside the jitted step) over ``expand_slots`` (the
slots the hops worked on), both as the process has counted them by the
end of the window (``train()`` adds a log window's counts at its flush;
every step works on the same shapes, so the ratio of the totals is the
ratio of a step). Tighter caps raise it. Silent on a program that has no
such counters, or that expanded nothing."""

import sys


def read(ctx):
    # the program's own ledger, where the harness has the program loaded
    telemetry = sys.modules.get("euler_tpu.telemetry")
    if telemetry is None:
        return None
    counters = telemetry.telemetry_json().get("counters", {})
    slots = counters.get("expand_slots", 0)
    if not slots:
        return None
    return 100.0 * counters.get("expand_edges", 0) / slots
