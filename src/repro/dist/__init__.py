"""Distribution layer: sharding rules, activation-sharding context, and the
`shard_map` entry point shared by every SPMD module."""
import functools

import jax

from repro.dist.sharding import (data_axes, logical_to_pspec, model_axes,
                                 param_pspecs, rules_for)

# Every SPMD body here reduces with explicit psums and replicates outputs
# by construction, so the varying-manual-axes check stays off.
shard_map = functools.partial(jax.shard_map, check_vma=False)

__all__ = ["shard_map", "data_axes", "logical_to_pspec", "model_axes",
           "param_pspecs", "rules_for"]
