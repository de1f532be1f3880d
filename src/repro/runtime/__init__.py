from repro.runtime.elastic import (
    elastic_restore,
    replan_for_mesh,
    replan_params_for_mesh,
    respawn_mesh,
    serving_restore,
)
from repro.runtime.fault_tolerance import (
    FAULT_COUNTER_KEYS,
    FaultInjector,
    FaultPolicy,
    FaultTolerantRunner,
    InjectedFault,
    LaunchFailedError,
    StragglerMonitor,
    export_fault_counters,
    parse_fault_plan,
    parse_fleet_plan,
)
from repro.runtime.replica import Replica, health_score, spawn_replica
from repro.runtime.spans import Spans

__all__ = [
    "FAULT_COUNTER_KEYS",
    "FaultInjector",
    "FaultPolicy",
    "FaultTolerantRunner",
    "InjectedFault",
    "LaunchFailedError",
    "Replica",
    "Spans",
    "StragglerMonitor",
    "elastic_restore",
    "export_fault_counters",
    "health_score",
    "parse_fault_plan",
    "parse_fleet_plan",
    "replan_for_mesh",
    "replan_params_for_mesh",
    "respawn_mesh",
    "serving_restore",
    "spawn_replica",
]
