"""Core MISS library: the paper's contribution as composable JAX modules.

Public API:
  MissConfig, run_l2miss         -- Algorithm 3 (host loop, jitted subroutines)
  run_maxmiss / run_lpmiss / run_ordermiss / run_diffmiss -- SS5 extensions
  fused_l2miss                   -- whole-loop on-device variant (beyond paper)
  fused_step / LaneState / LaneParams -- resumable step API (phase D serving)
  estimators.get / REGISTRY / get_by_id -- analytical functions f (id-indexed)
  GroupedData                    -- grouped dataset + inverted-index layout
  baselines                      -- BLK / SPS / IFocus / MiniBatch
"""
from . import baselines, bootstrap, error_model, estimators, extensions, sampling
from .estimators import Estimator, evaluate
from .extensions import (
    metric_value,
    order_bound,
    run_diffmiss,
    run_lpmiss,
    run_maxmiss,
    run_normalmiss,
    run_ordermiss,
)
from .framework import MissFailure, MissTrace, run_miss
from .fused import (
    FusedResult,
    LaneParams,
    LaneState,
    fused_l2miss,
    fused_l2miss_lanes,
    fused_step,
    init_lane_state,
    lanes_result,
    make_lane_params,
)
from .l2miss import MissConfig, exact_answer, run_l2miss
from .sampling import GroupedData

__all__ = [
    "Estimator", "FusedResult", "GroupedData", "LaneParams", "LaneState",
    "MissConfig", "MissFailure",
    "MissTrace", "baselines", "bootstrap", "error_model", "estimators",
    "evaluate", "exact_answer", "extensions", "fused_l2miss",
    "fused_l2miss_lanes", "fused_step",
    "init_lane_state", "lanes_result", "make_lane_params",
    "metric_value", "order_bound", "run_diffmiss",
    "run_l2miss", "run_lpmiss", "run_maxmiss", "run_miss",
    "run_normalmiss", "run_ordermiss",
    "sampling",
]
