from repro.serving.batcher import Batcher, BatchRecord, InferenceRequest
from repro.serving.engine import PodEngine
from repro.serving.gateway import Gateway
from repro.serving.libhas import LibHas, MemoryBudgetExceeded

__all__ = ["Batcher", "BatchRecord", "InferenceRequest", "PodEngine",
           "Gateway", "LibHas", "MemoryBudgetExceeded"]
