"""Device ms per tree in the fused build's ``frontier.split_pre`` scope,
scopes nested in it included: splitPre: the selection of open nodes
(``frontier.select``), the slot of every case and the stop tests
(``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_tree(ctx, "frontier.split_pre")
