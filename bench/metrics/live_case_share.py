"""Share of the case-steps of a build that were live, in %: the cases in an
open node summed over supersteps, over supersteps times N (the work a pass
over every case does)."""

from bench import scopes


def read(ctx):
    c = scopes.counts(ctx)
    if c is None or not c["frontier_supersteps"] or not c["frontier_cases"]:
        return None
    return 100.0 * c["frontier_live_case_steps"] / (
        c["frontier_supersteps"] * c["frontier_cases"])
