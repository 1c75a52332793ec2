from ..aqp.query import Request
from .batching import ContinuousBatcher
from .lane_pool import GroupPoolResponse, LanePool, PoolResponse
from .planner import Planner, PoolPlan, Route
from .session import AQPSession, SessionResponse, SessionTicket
from .slo import (AdmissionController, CostModel, DegradePlan, FairQueue,
                  eps_for_budget)
from .warm_cache import CachedAnswer, WarmCache, WarmEntry

# NOTE: ``Request`` here is the AQP serving request (aqp/query.py: Query +
# SLO envelope) -- what AQPSession.submit takes.  The LM token-batching
# request lives at ``repro.serve.batching.Request``; import it from the
# submodule.
__all__ = [
    "AQPSession", "AdmissionController", "CachedAnswer",
    "ContinuousBatcher", "CostModel", "DegradePlan", "FairQueue",
    "GroupPoolResponse", "LanePool", "Planner", "PoolPlan", "PoolResponse",
    "Request", "Route", "SessionResponse", "SessionTicket", "WarmCache",
    "WarmEntry", "eps_for_budget",
]
