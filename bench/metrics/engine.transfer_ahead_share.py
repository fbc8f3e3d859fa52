"""Engine: share of the window's decode-phase ``transfer`` spans whose DMA
was issued one linear ahead of the serving thread (percent; the spans'
``ahead`` attribute, host clock).  A program whose transfer spans carry
no ``ahead`` reads nothing."""


def read(ctx):
    flags = [(s.attrs or {}).get("ahead") for s in ctx.spans
             if s.track == "transfer" and ctx.w0 <= s.t0 <= ctx.w1
             and (s.attrs or {}).get("phase") == "decode"]
    flags = [f for f in flags if f is not None]
    if not flags:
        return None
    return 100.0 * sum(map(bool, flags)) / len(flags)
