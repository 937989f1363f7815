"""Logical-axis sharding rules (MaxText-style) with divisibility fallback."""
from repro.sharding.rules import (
    TRAIN_RULES,
    SERVE_RULES,
    SERVE_FSDP_RULES,
    profile_rules,
    resolve_pspec,
    tree_pspecs,
    tree_shardings,
    Param,
    split_params,
)
from repro.sharding.context import activation_sharding, act_shard

__all__ = [
    "TRAIN_RULES",
    "SERVE_RULES",
    "SERVE_FSDP_RULES",
    "profile_rules",
    "resolve_pspec",
    "tree_pspecs",
    "tree_shardings",
    "Param",
    "split_params",
    "activation_sharding",
    "act_shard",
]
