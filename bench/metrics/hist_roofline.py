"""Histogram kernel's share of its roofline: the least time the chip needs
for the counting C4.5 asks for (``bench.work.histogram``) over the device
time of the ``frontier_histogram`` kernel in the trace."""

from bench import work


def read(ctx):
    seconds = ctx["trace"]["kernel_s"].get("frontier_histogram")
    if not seconds or "histogram" not in ctx["work"]:
        return None
    share, bound = work.roofline_share(*ctx["work"]["histogram"], seconds,
                                       ctx["peak"])
    ctx["notes"].append(f"hist_roofline is bound by {bound}")
    return share
