"""Compaction's fill, in %: the live cases summed over supersteps, over the
cases the histogram kernel was given (the gather bucket, or N when nothing
is gathered)."""

from bench import scopes


def read(ctx):
    c = scopes.counts(ctx)
    if c is None or not c["frontier_hist_case_steps"]:
        return None
    return 100.0 * c["frontier_live_case_steps"] / c[
        "frontier_hist_case_steps"]
