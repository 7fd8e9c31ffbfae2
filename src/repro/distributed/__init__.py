"""Distribution layer: mesh-aware sharding rules, overlap-friendly
collectives, gradient compression, and pipeline parallelism."""
from repro.distributed.sharding import (
    batch_pspec, constrain, input_pspecs, logical_to_pspec, param_pspecs,
    shardings_for, ShardingRules,
)

__all__ = [
    "batch_pspec", "constrain", "input_pspecs", "logical_to_pspec",
    "param_pspecs", "shardings_for", "ShardingRules",
]
