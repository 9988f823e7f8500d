"""LMFAO core: the paper's layered aggregate engine in JAX.

Layers (paper Fig. 1): join tree -> find roots -> aggregate pushdown
(directional views) -> merge views -> group views -> multi-output plans ->
parallelization (shard_map) -> code generation (jit/XLA).
"""

from repro.core.aggregates import (Aggregate, Constant, Delta, Lambda, Param,
                                   Pow, ProductAgg, Query, Term, Var, agg,
                                   COUNT, query, sum_of, sum_prod, sum_sq)
from repro.core.engine import (BatchStats, CompiledBatch, Engine,
                               EngineDeprecationWarning)
from repro.core.jointree import JoinTree, materialize_bag
from repro.core.schema import (Attribute, DatabaseSchema, RelationSchema,
                               CATEGORICAL, CONTINUOUS, KEY, schema)

# the engine's spans (repro.obs.trace, which imports no jax) go to the JAX
# profiler's trace: every module that opens one imports this package first
from jax.profiler import TraceAnnotation as _TraceAnnotation
from repro.obs.trace import install as _install_spans

_install_spans(_TraceAnnotation)

# NOTE: the IVM subsystem (repro.core.ivm: MaintainedBatch, DeltaProgram) is
# deliberately not imported here — it depends on repro.data.relations, which
# imports repro.core.schema, and an eager import would cycle whenever
# repro.data is imported first.  Reach it via Engine.compile_incremental or
# `from repro.core.ivm import MaintainedBatch`.

__all__ = [
    "Aggregate", "Constant", "Delta", "Lambda", "Param", "Pow", "ProductAgg",
    "Query", "Term", "Var", "agg", "COUNT", "query", "sum_of", "sum_prod",
    "sum_sq", "BatchStats", "CompiledBatch", "Engine",
    "EngineDeprecationWarning", "JoinTree",
    "materialize_bag", "Attribute", "DatabaseSchema", "RelationSchema",
    "CATEGORICAL", "CONTINUOUS", "KEY", "schema",
]
