from .mesh import (
    EDGE_AXIS,
    MODEL_AXIS,
    VERTEX_AXIS,
    edge_sharding,
    make_mesh,
    replicated,
    vertex_sharding,
    vertex_shards,
)
from . import comm
from . import multihost
