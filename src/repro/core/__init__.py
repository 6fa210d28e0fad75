"""StarPlat DSL compiler — the paper's primary contribution.

Frontend: lexer → parser → AST (§2.4) → semantic analysis → IR.
Backends:  local (OpenMP analogue), distributed (MPI analogue, shard_map),
           pallas (CUDA analogue, TPU kernels).
"""
from ..schedule import DEFAULT_SCHEDULE, Schedule
from .api import (BoundProgram, CompiledProgram, bind_cache_clear,
                  bind_cache_size, bundled_programs, compile_bundled,
                  compile_cache_clear, compile_cache_size, compile_program,
                  load_program_source)
from .context import GraphContext, get_context, on_device, prepare

__all__ = ["BoundProgram", "CompiledProgram", "DEFAULT_SCHEDULE",
           "GraphContext", "Schedule", "bind_cache_clear", "bind_cache_size",
           "bundled_programs", "compile_bundled", "compile_cache_clear",
           "compile_cache_size", "compile_program", "get_context",
           "load_program_source", "on_device", "prepare"]
