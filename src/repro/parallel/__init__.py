from .ctx import ParallelCtx
from .rules import param_sharding, shard_params, state_sharding

__all__ = ["ParallelCtx", "param_sharding", "shard_params", "state_sharding"]
