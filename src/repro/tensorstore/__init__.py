"""RSS-versioned page stores: the paged mirror the OLAP kernels scan, the
plan IR served over it, and incrementally maintained views."""

from .paged import (init_store, visible_slots, snapshot_read_ref,
                    visible_slots_members, snapshot_read_members,
                    publish_page, as_page_range, gather_pages)
from .materialized import MaterializedView
from .mirror import PagedMirror, decode_value, encode_value
from .version_store import (AggOp, AggPlan, BatchPlan, ChainVersionStore,
                            GroupByPlan, MultiAggPlan, PagedVersionStore,
                            Plan, ScanPlan, VersionStore, agg_value,
                            apply_agg, apply_plan, finalize_agg, group_by,
                            plan_keys)

__all__ = [
    "init_store", "visible_slots", "snapshot_read_ref",
    "visible_slots_members", "snapshot_read_members", "publish_page",
    "as_page_range", "gather_pages",
    "PagedMirror", "MaterializedView", "encode_value", "decode_value",
    "VersionStore", "ChainVersionStore", "PagedVersionStore",
    "AggOp", "AggPlan", "BatchPlan", "MultiAggPlan", "GroupByPlan",
    "ScanPlan", "Plan",
    "agg_value", "apply_agg", "apply_plan", "finalize_agg", "group_by",
    "plan_keys",
]
