"""The repo's single seam with JAX's version-sensitive APIs.

The kernel surface (``ops/``, ``parallel/``, ``models/``) imports these
five names from here and nowhere else, so a JAX upgrade that renames one
is a one-file change.  Each resolves to the spelling of the one JAX this
repository is installed with (0.9.0 — recorded in
``artifacts/jax_api_drift.json``); there are no old-version branches.

==================  =======================================================
name                resolves to
==================  =======================================================
``CompilerParams``  ``jax.experimental.pallas.tpu.CompilerParams``
``axis_size``       ``jax.lax.axis_size``
``pcast``           ``jax.lax.pcast``
``shard_map``       ``jax.shard_map``
``cost_analysis``   ``lowered.compile().cost_analysis()`` on abstract
                    arguments, as ``dict | None``
==================  =======================================================

Everything resolves lazily (PEP 562): importing this module never
imports jax, so jax-free tooling (the analysis engine, the fleet
router's import path) can read :data:`SHIMMED_SYMBOLS` without paying
for a backend.  The ``compat-required`` analyzer rule closes the loop
statically — any direct use of a spelling listed in
:data:`SHIMMED_SYMBOLS` inside ``ops/``/``parallel/``/``models/`` is a
lint finding, and the ``jax-api-drift`` rule is a zero-baseline hard
gate, so a renamed symbol fails lint the commit it appears.

Upgrade workflow (docs/analysis.md "The compat workflow"): scanner
inventory -> adjust the entry here -> the drift gate goes back to zero.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Sequence

#: Every version-sensitive spelling — the installed one and the ones
#: earlier releases used — mapped to the name here that covers it.  This
#: dict is the contract shared with
#: :class:`fmda_tpu.analysis.compat_required.CompatRequiredRule`: a dotted
#: reference listed here appearing anywhere on the kernel surface outside
#: this module is a lint finding.  Importing it is jax-free by design
#: (the analyzer runs on jax-free hosts).
SHIMMED_SYMBOLS: Dict[str, str] = {
    "jax.experimental.pallas.tpu.CompilerParams": "CompilerParams",
    "jax.experimental.pallas.tpu.TPUCompilerParams": "CompilerParams",
    "jax.lax.axis_size": "axis_size",
    "jax.lax.pcast": "pcast",
    "jax.shard_map": "shard_map",
    "jax.experimental.shard_map.shard_map": "shard_map",
}

__all__ = [
    "CompilerParams",
    "SHIMMED_SYMBOLS",
    "axis_size",
    "cost_analysis",
    "pcast",
    "shard_map",
]


def _resolve_compiler_params() -> Any:
    """The ``pallas_call(compiler_params=...)`` dataclass."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams


def _resolve_axis_size() -> Callable[[str], int]:
    import jax

    return jax.lax.axis_size


def _resolve_pcast() -> Callable[..., Any]:
    import jax

    return jax.lax.pcast


def _resolve_shard_map() -> Callable[..., Any]:
    import jax

    return jax.shard_map


def _resolve_cost_analysis() -> Callable[..., Any]:
    """HLO cost accounting (FLOPs / bytes accessed) for a jitted call.

    Returns ``probe(jitted, args, kwargs) -> dict | None``: the call
    is re-lowered against **abstract** arguments (``ShapeDtypeStruct``
    per array leaf — the concrete buffers may already be donated and
    deleted by the time the compile ledger probes), compiled, and the
    compiled object's ``cost_analysis`` dict (``{"flops": ...,
    "bytes accessed": ...}``) is returned; ``None`` when XLA reports no
    costs for the program.
    """
    import jax

    def _abstract(leaf):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            return jax.ShapeDtypeStruct(tuple(shape), dtype)
        return leaf

    def cost_analysis(jitted, args, kwargs=None) -> Any:
        kwargs = kwargs or {}
        a_args, a_kwargs = jax.tree_util.tree_map(_abstract,
                                                  (args, kwargs))
        cost = jitted.lower(*a_args, **a_kwargs).compile().cost_analysis()
        return dict(cost) if cost else None

    return cost_analysis


_RESOLVERS: Dict[str, Callable[[], Any]] = {
    "CompilerParams": _resolve_compiler_params,
    "axis_size": _resolve_axis_size,
    "cost_analysis": _resolve_cost_analysis,
    "pcast": _resolve_pcast,
    "shard_map": _resolve_shard_map,
}


def __getattr__(name: str) -> Any:
    """Resolve on first access and cache in the module dict (later
    lookups never re-enter here)."""
    resolver = _RESOLVERS.get(name)
    if resolver is None:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}")
    value = resolver()
    globals()[name] = value
    return value


def __dir__() -> Sequence[str]:
    return sorted(set(globals()) | set(_RESOLVERS))
