"""Device milliseconds per BFS level in the scope ``bfs.claim`` of
`core/bfs.py` (the parent claims through `atomics.execute`: for CAS the
table-only scatter): the sum over the traversals (the program's host spans
``bfs.traversal``) wholly inside the traced window, over the sum of their
level counts, which each span carries."""

from bench import scopes


def read(trace, record, ctx):
    return scopes.per_level_ms(trace, "bfs.claim")
