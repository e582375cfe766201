from .types import SampledEdge, SignedVertex, TriangleEstimate
from .profiling import StreamProfiler, WindowStats, profiled
from .config import EngineConfig
