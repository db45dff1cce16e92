"""Legacy public API of the DPMR core.

Prefer `repro.api` (the typed `DPMREngine` façade + strategy registry) and
`repro.data` (the DataSource registry + ShardedLoader); this module keeps
flat re-exports of the core primitives working. The deprecated fn-dict
training entry points (`dpmr_train`, `dpmr_train_sgd`, `dpmr_classify`,
`evaluate` from the old `core.sparse_lr`) completed their one-release
deprecation and were REMOVED — see the migration table in CHANGES.md.
"""
from repro.api.engine import hot_ids_from_corpus
from repro.core.dpmr import (
    DPMRState,
    StepFns,
    capacity,
    init_state,
    make_schedule,
    make_step_fns,
    num_shards,
    optimize,
    padded_features,
)
from repro.core.fsdp import dpmr_dense_linear, fsdp_specs
from repro.core.hot_sharding import (
    load_imbalance,
    select_hot,
    split_hot,
)
from repro.core.sparse import (
    Routing,
    combine_grads,
    owner_accumulate,
    owner_apply,
    route_build,
    route_return,
)

__all__ = [
    "DPMRState", "Routing", "StepFns", "capacity", "combine_grads",
    "dpmr_dense_linear", "fsdp_specs",
    "hot_ids_from_corpus", "init_state", "load_imbalance", "make_schedule",
    "make_step_fns", "num_shards", "optimize", "owner_accumulate",
    "owner_apply", "padded_features", "route_build", "route_return",
    "select_hot", "split_hot",
]
