"""Device time per traced step under the program's ``draw`` scope: the
key derivation, the routing and the draw kernel (``draw.kernel_ms`` is
the kernel alone), fullest chip."""

from benchmark import scopes

# the scopes this reader claims (benchmark/scopes.py reads this line)
SCOPES = ("draw",)


def read(ctx):
    return scopes.scopes_ms(ctx, *SCOPES)
