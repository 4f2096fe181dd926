"""Device (TPU-native) CER engine: symbolic tables + semiring scan + tECS."""
from ..kernels.window import DeviceWindow, resolve_window, window_overflow
from .encoder import EventEncoder
from .engine import VectorEngine, VectorQueryTables
from .multiquery import (MultiQueryEngine, Packing, PackingInvariantError,
                         build_packing, check_packing_invariants)
from .partitioned import (KeyPoolEngine, PartitionedStreamingEngine,
                          PartitionStats, PoolOverflowError)
from .streaming import StreamingVectorEngine, migrate_packed_arrays
from .symbolic import SymbolicCEA, compile_symbolic
from .tecs_arena import ArenaOverflow, ArenaSnapshot

__all__ = ["DeviceWindow", "EventEncoder", "VectorEngine",
           "VectorQueryTables", "MultiQueryEngine", "Packing",
           "PackingInvariantError", "build_packing",
           "check_packing_invariants", "KeyPoolEngine",
           "PartitionedStreamingEngine", "PartitionStats",
           "PoolOverflowError", "StreamingVectorEngine",
           "migrate_packed_arrays", "SymbolicCEA",
           "compile_symbolic", "ArenaOverflow", "ArenaSnapshot",
           "resolve_window", "window_overflow"]
