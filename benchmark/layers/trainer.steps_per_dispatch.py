"""Steps a dispatch of the training thread over the window: the sum of
the program's ``dispatch_steps`` value histogram over its count (one
sample a dispatch, its steps). Ten where ``train()`` runs a chunk of
device-sampled steps in one call, a little under where a log window's
end cuts a chunk short, one where the host samples. None on a program
that records no such histogram."""


def read(ctx):
    count, steps = ctx.phase("dispatch_steps")
    return steps / count if count > 0 else None
