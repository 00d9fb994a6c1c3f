"""Probabilistic-scheduling request router (serving plane) and the
closed-loop control pieces (EWMA estimators + batched re-planning)."""

from .router import (
    AdaptiveReplanner,
    EwmaMomentEstimator,
    EwmaRateEstimator,
    HierarchicalReplanner,
    ReplicaPool,
    Router,
    batched_rollout_scores,
    simulate_serving,
)
