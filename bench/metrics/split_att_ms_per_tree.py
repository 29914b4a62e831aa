"""Device ms per tree in the fused build's ``frontier.split_att`` scope,
scopes nested in it included: splitAtt: compaction (``frontier.compact``),
both Pallas kernels and the pick of the best attribute
(``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_tree(ctx, "frontier.split_att")
