from .mesh import make_mesh  # noqa: F401
from .sharded_agg import ShardedAggregator  # noqa: F401
