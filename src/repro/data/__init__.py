"""Data substrate: columnar relations, synthetic schemas, engine-backed
feature statistics."""

from repro.data.relations import (Database, DeltaBatchUpdate, Relation,
                                  RelationDelta, ResidentRelation,
                                  apply_delta, from_numpy, sort_by)

__all__ = ["Database", "DeltaBatchUpdate", "Relation", "RelationDelta",
           "ResidentRelation", "apply_delta", "from_numpy", "sort_by"]
