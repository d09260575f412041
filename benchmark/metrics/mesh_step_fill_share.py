"""How full the sharded aggregate's steps are: the rows the window's steps
carried over the rows they had room for (shards times the per-shard batch),
both as the program's agg.dispatch spans carry them (rows, room), of the
fullest sharded aggregate: the task whose steps carried the most rows."""
from harness import roofline_mesh


def read(run):
    by_node: dict = {}
    for s in roofline_mesh.mesh_steps(run):
        by_node.setdefault(s.node, []).append(s)
    if not by_node:
        return None
    fullest = max(by_node.values(), key=lambda spans: sum(s.args["rows"] for s in spans))
    return 100.0 * sum(s.args["rows"] for s in fullest) / sum(s.args["room"] for s in fullest)
