from .engine import Engine, ServeConfig
from .pool import (
    EnginePool,
    EngineSlot,
    WorkerLost,
    WorkerSpec,
    engine_factory,
    null_engine_factory,
)
from .queue import AdmissionQueue, Request, TenantTier, class_mix, workload_class
from .router import Dispatch, Router, router_machine
from .watchdog import DeadlineWatchdog
__all__ = ["AdmissionQueue", "DeadlineWatchdog", "Dispatch", "Engine",
           "EnginePool", "EngineSlot", "Request", "Router", "ServeConfig",
           "TenantTier", "WorkerLost", "WorkerSpec", "class_mix",
           "engine_factory", "null_engine_factory", "router_machine",
           "workload_class"]
