"""Device ms per tree in the fused build's ``frontier.split_post`` scope,
scopes nested in it included: splitPost: child allocation, the scatter of
node results and the routing of cases (``frontier.route``)
(``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_tree(ctx, "frontier.split_post")
