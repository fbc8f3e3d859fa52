"""Kernels: the Mamba decode state update's share of its roofline, in
percent.  Its time is the device trace's: the operations of the two
decode kernels, ``ssm_state_conv`` and ``ssm_state_scan``.  Its least
time is the configuration's count of the state each decode row must read
and write once in each SSM layer (``ssm_state_bytes_per_row`` times
``ssm_layers``) over the chip's memory bandwidth; the update's operations
(about five a state element) are three orders of magnitude under the
bytes at the chip's balance and are not counted."""

from bench.harness.flops import roofline_seconds

# the Pallas kernels' custom calls, one of each per SSM layer and step
PATTERN = r"^%ssm_state_(conv|scan)(\.\d+)? = "


def read(ctx):
    c = ctx.counts
    if "ssm_layers" not in c or ctx.peaks is None:
        return None
    kt = ctx.kernel_time(PATTERN)
    if kt is None or kt[0] <= 0:
        return None
    rows = sum(r for t0, _, r, _ in ctx.layer.decode if ctx.in_window(t0))
    nbytes = float(rows * c["ssm_layers"] * c["ssm_state_bytes_per_row"])
    return 100.0 * roofline_seconds(0.0, nbytes, ctx.peaks) / kt[0]
