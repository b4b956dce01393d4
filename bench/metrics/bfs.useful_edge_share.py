"""Share of the edge reads a traversal makes that find their source in
the frontier, in percent: every level of `core/bfs.py` reads every
directed edge, and an edge is useful on the one level its source joins the
frontier.  ``edges_traversed / (levels x directed edges)``, the counts
summed over the traversals (the program's host spans ``bfs.traversal``,
which carry both counts) wholly inside the traced window."""

from bench import scopes


def read(trace, record, ctx):
    done = scopes.traversals(trace)
    edges = record.extra.get("directed_edges")
    if not done or not edges:
        return None
    levels = sum(int(s.args["levels"]) for s in done)
    useful = sum(int(s.args["edges_traversed"]) for s in done)
    return 100.0 * useful / (levels * edges) if levels else None
