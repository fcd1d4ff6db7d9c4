from repro.distributed.ctx import (  # noqa: F401
    constrain, current_mesh, current_rules, use_mesh,
)
from repro.distributed.sharding import (  # noqa: F401
    ShardingRules, batch_shardings, cache_shardings, mesh_axis_label,
    param_shardings, param_specs, rules_for_mesh, shard_params,
    sharding_summary,
)
