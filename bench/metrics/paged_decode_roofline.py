"""Kernels: the paged decode-attention kernel's share of its roofline,
in percent.  Its time is the device trace's; the least time is the
configuration's count of the keys and values each call must read (every
decode call runs the kernel once per paged layer) against the chip's
bandwidth and peak."""

from bench.harness.flops import (paged_decode_bytes, paged_decode_flops,
                                 roofline_seconds)

# the paged-attention kernel's custom call on one query per row (decode);
# its prefill calls carry a whole chunk of queries: f32[40,256,128]
PATTERN = r"^%_paged(\.\d+)? = \w+\[\d+,1,\d+\]"


def read(ctx):
    kt = ctx.kernel_time(PATTERN)
    if kt is None or kt[0] <= 0 or ctx.peaks is None:
        return None
    c = ctx.counts
    least = sum(c["paged_layers"] * roofline_seconds(
                    paged_decode_flops(c, lens), paged_decode_bytes(c, lens),
                    ctx.peaks)
                for t0, _, _, lens in ctx.layer.decode if ctx.in_window(t0))
    return 100.0 * least / kt[0]
