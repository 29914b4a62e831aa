"""Split-gain kernel's share of its roofline: the least time for scoring
the tested nodes' histograms (``bench.work.split_gain``) over the device
time of the ``split_gain`` kernel in the trace."""

from bench import work


def read(ctx):
    seconds = ctx["trace"]["kernel_s"].get("split_gain")
    if not seconds or "split_gain" not in ctx["work"]:
        return None
    share, bound = work.roofline_share(*ctx["work"]["split_gain"], seconds,
                                       ctx["peak"])
    ctx["notes"].append(f"gain_roofline is bound by {bound}")
    return share
