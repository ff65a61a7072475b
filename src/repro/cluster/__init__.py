"""Simulated serving substrate: ``SimServer`` and the calibrated cost
and network models ``SimBackend`` runs on.

Lazy exports (PEP 562): ``repro.cluster.network`` is a pure-Python
module the import-light ``repro.analysis`` protocol checker loads in a
bare venv; eager re-exports here would pull the numpy/jax-backed
cost model with it.
"""
_EXPORTS = {
    "ServerModel": "costmodel", "co_serving_slowdown": "costmodel",
    "make_server": "costmodel", "profile_operating_points": "costmodel",
    "NetworkModel": "network",
    "SimServer": "server",
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
