"""Output tokens delivered to clients inside the window, per second of
the window (host clock, stamped by each request's stream callback).  The
window closes when the step in flight at its deadline has returned, so
it holds whole steps and all of their time."""


def read(ctx):
    return ctx.window_tokens() / (ctx.w1 - ctx.w0)
