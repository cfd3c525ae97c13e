"""``train()`` from its entry to its loop, less what the other set-up spans
and the compile listener claim inside it: ``init_state`` where no state
is handed in, ``state_sharding``, the placement of the state
(``put_global(consume=True)``'s own share is ``setup.upload_s``),
``describe_state``, the checkpoint's restore: the program's span
``setup_state_place``, seconds before the window opened."""

from benchmark import setup_spans


def read(ctx):
    return setup_spans.seconds(ctx, "setup_state_place")
