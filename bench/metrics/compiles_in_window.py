"""JAX runtime: backend compiles reported inside the window (JAX's
monitoring events).  Every shape the window meets is compiled or loaded
from the persistent cache during set-up, so this should read 0."""


def read(ctx):
    return ctx.clock.count(ctx.w0, ctx.w1)["compiles"]
