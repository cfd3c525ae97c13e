"""Set-up by the program's own spans: seconds of one eg_phase histogram
as the process had recorded it when the window opened (``Hook._snapshot``
-> ``ctx.at_open["phases"]``): everything loaded, exported, built, packed,
uploaded, placed, traced and lowered inside ``setup_s``, the harness's
own calls into the program and the reference's ``drawn_hops`` jits
included. The histograms hold self times on a thread (a span that holds
another records what is left of it: OBSERVABILITY.md "Set-up phases"),
so the readers of this file add up to no more than the wall time they
cover. None on a program that has no such phase, or recorded nothing
under it."""


def seconds(ctx, phase: str):
    count, total_us = ctx.at_open["phases"].get(phase, (0, 0))
    return total_us / 1e6 if count else None
