"""Mean of the program's eg_phase ``dispatch`` leaf over the window: the
jitted step call, from entering it to its return. With
``trainer.fence_wait_ms`` it makes up ``trainer.fenced_dispatch_ms``."""


def read(ctx):
    return ctx.phase_mean_ms("dispatch")
