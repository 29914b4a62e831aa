"""Device busy time per tree outside the Pallas kernels
(``frontier_histogram``, ``split_gain``, ``live_case_ids``): splitPre, the
compaction gathers, splitPost and the copies, in ms."""

KERNELS = ("frontier_histogram", "split_gain", "live_case_ids")


def read(ctx):
    tr = ctx["trace"]
    units = ctx.get("units")
    if not units or tr["busy_s"] <= 0:
        return None
    inside = sum(tr["kernel_s"].get(k, 0.0) for k in KERNELS)
    return 1e3 * (tr["busy_s"] - inside) / units
