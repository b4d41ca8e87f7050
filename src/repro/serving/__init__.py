"""Public surface of the ``repro.serving`` package.

One coherent import point for the GCN serving stack:

* ``GCNServingEngine`` — the mesh-wide deadline-aware engine, with
  ``GCNServingEngine(policy=...)`` as the scheduling seam;
* ``SchedulingPolicy`` / ``HeuristicPolicy`` / ``LearnedServiceTimePolicy``
  plus the policy state/decision types (``PolicyState``, ``GraphState``,
  ``PlaceDecision``, ``ReplicaDecision``, ``ShedDecision``,
  ``DispatchOrder``);
* ``MeshPlacer`` / ``Placement`` — placement bookkeeping;
* ``SubmitTicket`` with its ``ACCEPTED``/``REJECTED``/``SHED`` statuses;
* the typed error family under ``ServingError``.

Everything resolves lazily (PEP 562), so ``import repro.serving`` stays
cheap and the historical deep import paths
(``repro.serving.gcn_engine.UnknownGraphError`` etc.) keep working —
those modules re-export from their new homes.
"""

from __future__ import annotations

from repro.lazyexports import lazy_exports

__all__ = [
    "ACCEPTED",
    "AdmitReport",
    "DispatchOrder",
    "FlushError",
    "GCNServingEngine",
    "GraphState",
    "HeuristicPolicy",
    "LearnedServiceTimePolicy",
    "MeshPlacer",
    "Placement",
    "PlaceDecision",
    "PolicyState",
    "REJECTED",
    "ReplicaDecision",
    "RequestFailure",
    "SHED",
    "SchedulingPolicy",
    "ServingError",
    "ShedDecision",
    "SubmitTicket",
    "UnknownGraphError",
    "UnsupportedArchitectureError",
    "UpdateReport",
]

__getattr__, __dir__ = lazy_exports(
    __name__,
    {
        # engine
        "GCNServingEngine": "repro.serving.gcn_engine",
        "AdmitReport": "repro.serving.gcn_engine",
        "UpdateReport": "repro.serving.gcn_engine",
        # placement
        "MeshPlacer": "repro.serving.placement",
        "Placement": "repro.serving.placement",
        # scheduling policies
        "SchedulingPolicy": "repro.serving.policy",
        "HeuristicPolicy": "repro.serving.policy",
        "LearnedServiceTimePolicy": "repro.serving.policy",
        "PolicyState": "repro.serving.policy",
        "GraphState": "repro.serving.policy",
        "PlaceDecision": "repro.serving.policy",
        "ReplicaDecision": "repro.serving.policy",
        "ShedDecision": "repro.serving.policy",
        "DispatchOrder": "repro.serving.policy",
        # tickets + errors
        "SubmitTicket": "repro.serving.types",
        "ACCEPTED": "repro.serving.types",
        "REJECTED": "repro.serving.types",
        "SHED": "repro.serving.types",
        "ServingError": "repro.serving.errors",
        "UnknownGraphError": "repro.serving.errors",
        "RequestFailure": "repro.serving.errors",
        "FlushError": "repro.serving.errors",
        "UnsupportedArchitectureError": "repro.serving.errors",
    },
    globals(),
)
