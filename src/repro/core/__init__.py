"""Cascade — the paper's contribution: utility-driven speculative decoding
management for MoE serving."""

from .controller import (CascadeController, StaticKController,
                         cascade_for_model)
from .cost_model import (Hardware, Precision, TPU_V5E, RTX_6000_ADA,
                         hardware_for_device_kind,
                         batch_iteration_time, expected_unique_experts,
                         expected_unique_experts_batch, iteration_bytes,
                         iteration_flops, iteration_time, draft_time,
                         sample_time, kv_bytes_per_token)
from .cost_model import (BatchCostOracle, Calibration, ExpertPlacement,
                         a2a_bytes, expected_emitted,
                         expected_emitted_curve,
                         expected_unique_experts_sharded,
                         fetch_hide_schedule, fetch_time_layered,
                         moe_hide_fracs)
from .manager import BASELINE, TEST, SET, CascadeConfig, SpeculationManager
from .planner import (ADMIT, DEFER, SHED, AdmissionConstraint,
                      AdmissionDecision, BatchPlan, BatchSpecPlanner,
                      BreakEvenConstraint, DraftYieldModel,
                      FetchDeadlineConstraint, GrantConstraint,
                      MemoryCapConstraint, PlanDecision, PlannerConfig,
                      PredictiveTTFTAdmission, SLOTpotConstraint,
                      greedy_allocate)
from .residency import (ResidencyState, expert_hbm_bytes,
                        moe_layer_count)
from .slo import (LATENCY, THROUGHPUT, RequestSLO, tpot_within,
                  ttft_violated)
from .utility import IterationRecord, UtilityAnalyzer

__all__ = [
    "CascadeController", "StaticKController", "CascadeConfig",
    "SpeculationManager", "UtilityAnalyzer", "IterationRecord",
    "Hardware", "Precision", "TPU_V5E", "RTX_6000_ADA",
    "hardware_for_device_kind",
    "expected_unique_experts",
    "expected_unique_experts_batch", "batch_iteration_time",
    "BatchCostOracle", "Calibration", "iteration_bytes", "iteration_flops",
    "iteration_time", "draft_time", "sample_time", "kv_bytes_per_token",
    "BASELINE", "TEST", "SET", "cascade_for_model",
    "BatchSpecPlanner", "BatchPlan", "PlanDecision", "PlannerConfig",
    "expected_emitted", "expected_emitted_curve", "greedy_allocate",
    "ExpertPlacement", "expected_unique_experts_sharded", "a2a_bytes",
    "RequestSLO", "LATENCY", "THROUGHPUT", "tpot_within", "ttft_violated",
    "GrantConstraint", "BreakEvenConstraint", "SLOTpotConstraint",
    "MemoryCapConstraint", "FetchDeadlineConstraint",
    "AdmissionConstraint", "AdmissionDecision", "PredictiveTTFTAdmission",
    "ADMIT", "DEFER", "SHED",
    "ResidencyState", "expert_hbm_bytes", "moe_layer_count",
    "fetch_hide_schedule", "fetch_time_layered", "moe_hide_fracs",
    "DraftYieldModel",
]
