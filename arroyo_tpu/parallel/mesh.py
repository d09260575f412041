"""Device mesh construction for key-space sharding.

The reference scales by hash-partitioning the key space across subtasks
connected by a TCP shuffle (crates/arroyo-worker/src/network_manager.rs).
The TPU-native equivalent shards the key space across a 1-D device mesh
("data" axis); the repartition becomes an all_to_all over ICI inside a
shard_map'd step (see sharded_agg.py). Multi-host extends the same mesh over
DCN via jax.distributed — same program, bigger mesh.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

KEY_AXIS = "data"


def make_mesh(n_devices: Optional[int] = None, axis: str = KEY_AXIS) -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(f"need {n_devices} devices, have {len(devs)}")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))
