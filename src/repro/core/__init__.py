"""LORASERVE core: the paper's contribution — rank- and demand-aware
dynamic adapter placement (Algorithm 1), phi-weighted routing, and the
distributed adapter pool.

Exports resolve lazily (PEP 562): ``repro.core.pool`` / ``.routing`` /
``.types`` are pure-Python control-plane modules, and the import-light
``repro.analysis`` protocol checker must be able to load them in a bare
venv without dragging in the jax-backed siblings (baselines,
orchestrator) that eager re-exports would import.
"""
_EXPORTS = {
    "ContiguousPolicy": "baselines", "LoraservePolicy": "baselines",
    "POLICIES": "baselines", "RandomPolicy": "baselines",
    "ToppingsPolicy": "baselines",
    "DemandEstimator": "demand",
    "ClusterOrchestrator": "orchestrator",
    "assign_loraserve": "placement",
    "AdapterStore": "pool", "FetchPlan": "pool",
    "Phase": "request", "Request": "request", "ServeRequest": "request",
    "RetiredServerError": "routing", "RoutingTable": "routing",
    "UnknownAdapterError": "routing",
    "AdapterInfo": "types", "Placement": "types",
    "PlacementContext": "types", "PlacementStats": "types",
    "servers_to_adapters": "types",
}

__all__ = list(_EXPORTS)


def __getattr__(name):
    import importlib
    mod = _EXPORTS.get(name)
    if mod is not None:
        return getattr(importlib.import_module(f".{mod}", __name__), name)
    try:                         # plain submodule access (pkg.network)
        return importlib.import_module(f".{name}", __name__)
    except ImportError:
        raise AttributeError(f"module {__name__!r} has no attribute "
                             f"{name!r}") from None


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
