from repro.core.request import Phase, Request, ServeRequest

from .backend import EngineBackend, ServingBackend, SimBackend
from .cluster import (ClusterEvent, ClusterReport, LoRAServeCluster,
                      ServeResult)
from .engine import ServingEngine
from .metrics import MetricsCollector, percentile
from .scheduler import replay
from .paging import OutOfPages, UnifiedPagePool

__all__ = ["EngineBackend", "ServingBackend", "SimBackend",
           "ClusterEvent", "ClusterReport", "LoRAServeCluster",
           "ServeResult", "ServingEngine", "MetricsCollector",
           "percentile", "Phase", "Request", "ServeRequest", "replay",
           "OutOfPages", "UnifiedPagePool"]
