"""Seconds from the start of the process to the opening of the window:
weights, host copies and plans, warm-up with its compiles or cache
loads, and the load's own ramp or pre-roll."""


def read(ctx):
    return ctx.setup_s
