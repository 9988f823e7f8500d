"""Serving layer.

``views.py`` — the aggregate engine's serving front end: epoch-pinned,
snapshot-consistent reads over incrementally maintained views
(:class:`~repro.serve.views.ViewServer`), the piece that turns the engine
into a long-lived service under concurrent reads and update streams.

``router.py`` / ``planner.py`` — ad-hoc query serving (DESIGN.md §13): a
signature router answering *arbitrary* group-by aggregates from the
session's views (exact match / subsumption re-aggregation / verified
compile-and-cache), driven by an adaptive planner over the signature
lattice.  Reached through ``Database.query`` / ``ViewServer.query``.
"""

from repro.core.ivm import EpochEvictedError
from repro.serve.planner import AdaptivePlanner, Candidate, RoutePlan
from repro.serve.router import QueryRouter, RouteResult
from repro.serve.views import EpochView, ViewServer

__all__ = ["AdaptivePlanner", "Candidate", "EpochEvictedError", "EpochView",
           "QueryRouter", "RoutePlan", "RouteResult", "ViewServer"]
