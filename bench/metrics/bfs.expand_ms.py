"""Device milliseconds per BFS level in the scope ``bfs.expand`` of
`core/bfs.py` (the gather ``frontier[src]`` over every directed edge and
the candidates): the sum over the traversals (the program's host spans
``bfs.traversal``) wholly inside the traced window, over the sum of their
level counts, which each span carries."""

from bench import scopes


def read(trace, record, ctx):
    return scopes.per_level_ms(trace, "bfs.expand")
