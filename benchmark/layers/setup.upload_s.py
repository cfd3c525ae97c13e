"""The host's part of handing the per-node tables to the device:
``jnp.asarray`` of each exported table, ``pad_tables_for_mesh``,
``put_global`` with the re-lay into a pinned layout: the program's span
``setup_upload``, seconds before the window opened. The span ends where
the call returns; what the runtime still copies after that runs on under
the next stage and is in no span (the program adds no fence for it)."""

from benchmark import setup_spans


def read(ctx):
    return setup_spans.seconds(ctx, "setup_upload")
