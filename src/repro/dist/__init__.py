"""Distributed-execution substrate for the fastest-k / beta-scaled runtime.

The paper's scheme (adaptive number of waited-for workers k, adaptive
per-worker computation load beta) only pays off once it is wired into a
real sharded runtime. This package provides that wiring:

  sharding.py          — logical-axis -> mesh-axis rules, PartitionSpec
                         derivation, and the ambient activation-sharding
                         context used by the model code,
  collectives.py       — masked fastest-k aggregation: the worker mask
                         enters the loss as DATA, so dropping stragglers
                         never triggers a recompile (DESIGN.md §2.3),
  compression.py       — int8 gradient codec + error feedback (the
                         paper's "slight increase in communication load"
                         is bought back by compressing the result),
  pipeline_parallel.py — GPipe-style pipeline stage for depth sharding.

Everything here is pure JAX (no pallas): the collectives are expressed
as weighted reductions and sharding constraints so GSPMD chooses the
actual all-reduce/all-gather schedule.
"""

from .collectives import contributors, example_weights, masked_weighted_ce
from .compression import Int8Codec, ef_compress_tree
from .sharding import (
    DEFAULT_RULES,
    FSDP_POD_RULES,
    PURE_DP_RULES,
    SP_DECODE_RULES,
    ShardingRules,
    activation_sharding,
    batch_pspec,
    constrain_batch,
    constrain_logical,
    logical_to_pspec,
    make_sharding_fn,
)

__all__ = [
    "ShardingRules",
    "DEFAULT_RULES",
    "FSDP_POD_RULES",
    "PURE_DP_RULES",
    "SP_DECODE_RULES",
    "logical_to_pspec",
    "batch_pspec",
    "make_sharding_fn",
    "activation_sharding",
    "constrain_batch",
    "constrain_logical",
    "contributors",
    "example_weights",
    "masked_weighted_ce",
    "Int8Codec",
    "ef_compress_tree",
]
