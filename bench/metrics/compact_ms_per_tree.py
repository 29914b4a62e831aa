"""Device ms per tree in the fused build's ``frontier.compact`` scope,
scopes nested in it included: compaction: the live-case count, the
``live_case_ids`` stream compaction of the live case ids and the gathers
(``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_tree(ctx, "frontier.compact")
