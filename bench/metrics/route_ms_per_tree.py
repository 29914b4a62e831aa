"""Device ms per tree in the fused build's ``frontier.route`` scope, scopes
nested in it included: the routing of every case to its child
(``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_tree(ctx, "frontier.route")
