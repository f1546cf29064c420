from repro.serve.engine import (EngineConfig, PlacementBuffer, ServeStats,
                                SimCacheEngine, bucket_size, prefill_pieces)
from repro.serve.stream import (DriverStats, RequestStream, StreamDriver,
                                StreamSpec)

__all__ = ["SimCacheEngine", "EngineConfig", "ServeStats",
           "PlacementBuffer", "bucket_size", "prefill_pieces",
           "StreamDriver", "StreamSpec", "RequestStream", "DriverStats"]
