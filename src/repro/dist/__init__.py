from .sharding import (  # noqa: F401
    MeshContext, MeshSpec, ShardingPolicy, make_mesh, constraint,
    current_policy, named_sharding_tree, param_specs, shard_map, use_policy,
)
