"""Halo core — the paper's contribution (parser → optimizer → processor).

Public API:

    from repro.core import (
        parse_workflow, consolidate, CostModel, EpochDPSolver,
        SolverConfig, SystemState, ExecutionPlan,
    )

    graph = parse_workflow(workflow_dict)          # §3 Parser
    batch = consolidate(graph, bindings)           # cross-query consolidation
    mega  = consolidate_multi([(graph_a, binds_a),  # cross-TEMPLATE mega-DAG
                               (graph_b, binds_b)])  # (DESIGN.md §8.1)
    cm    = CostModel(graph, HARDWARE["h200"], models, ...)
    plan  = EpochDPSolver(graph.llm_dag(), cm,
                          SolverConfig(num_workers=3)).solve()   # §4
    # runtime execution: repro.runtime.Processor                  # §5
"""
from repro.core.coalesce import CoalesceTable, canonical_signature
from repro.core.consolidate import (
    ConsolidatedGraph, MultiConsolidatedGraph, consolidate, consolidate_multi,
)
from repro.core.cost_model import (
    A100, H100, H200, HARDWARE, PAPER_MODELS, TPU_V5E, CostModel,
    EpochWeights, HardwareCalibration, HardwareProfile, LLMProfile,
    OperatorProfiler, hardware_for_device_kind, profile_from_config,
)
from repro.core.graphspec import GraphSpec, LLMDag, NodeSpec, NodeType
from repro.core.optimality import optimality_score
from repro.core.oracle import BranchAndBoundOracle
from repro.core.parser import parse_workflow, render, static_signature
from repro.core.plan import Epoch, ExecutionPlan
from repro.core.schedulers import (
    SCHEDULERS, heft_plan, opwise_plan, random_plan, round_robin_plan,
)
from repro.core.solver import EpochDPSolver, SolverConfig
from repro.core.state import SystemState, WorkerContext

__all__ = [
    "CoalesceTable", "canonical_signature", "ConsolidatedGraph",
    "MultiConsolidatedGraph", "consolidate", "consolidate_multi",
    "CostModel", "EpochWeights", "HardwareCalibration",
    "HardwareProfile",
    "LLMProfile", "OperatorProfiler", "profile_from_config", "HARDWARE",
    "hardware_for_device_kind",
    "PAPER_MODELS", "H200", "H100", "A100", "TPU_V5E", "GraphSpec",
    "LLMDag", "NodeSpec", "NodeType", "optimality_score",
    "BranchAndBoundOracle", "parse_workflow", "render", "static_signature",
    "Epoch", "ExecutionPlan", "SCHEDULERS", "heft_plan", "opwise_plan",
    "random_plan", "round_robin_plan", "EpochDPSolver", "SolverConfig",
    "SystemState", "WorkerContext",
]
