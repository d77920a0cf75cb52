"""The repo's single seam with JAX's version-sensitive APIs.

The kernel surface (``ops/``, ``parallel/``, ``models/``) imports the
first four names from here and nowhere else, and the compile ledger
(``obs/device.py``) the last three, so a JAX upgrade that renames one is
a one-file change.  Each resolves to the spelling of the one JAX this
repository is installed with (0.9.0 — recorded in
``artifacts/jax_api_drift.json``); there are no old-version branches.

======================  ===================================================
name                    resolves to
======================  ===================================================
``CompilerParams``      ``jax.experimental.pallas.tpu.CompilerParams``
``axis_size``           ``jax.lax.axis_size``
``pcast``               ``jax.lax.pcast``
``shard_map``           ``jax.shard_map``
``abstract_signature``  a call's ``(args, kwargs)`` as ``ShapeDtypeStruct``
                        leaves that lower to the program the call ran
``program_analysis``    one ``lower().compile()`` on that signature:
                        ``cost_analysis()`` and ``memory_analysis()``
``cost_analysis``       the ``cost`` half of it, as ``dict | None``
======================  ===================================================

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
    "abstract_signature",
    "axis_size",
    "cost_analysis",
    "pcast",
    "program_analysis",
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


def _resolve_abstract_signature() -> Callable[..., Any]:
    """``abstract_signature(args, kwargs) -> (args, kwargs)`` with every
    array leaf a ``ShapeDtypeStruct``: shape, dtype, weak type, and the
    leaf's sharding **where the leaf is committed and only there**.

    That is the one choice under which ``jitted.lower(...)`` finds the
    lowering and the executable the call itself made (jax 0.9.0): a
    sharding on every leaf, or on none, is another key in jax's caches
    and lowers and compiles the program a second time.  A donated leaf
    may be read after the call: a deleted array keeps its shape, dtype
    and sharding.  Leaves that are already ``ShapeDtypeStruct`` and
    leaves that are no arrays (Python scalars: their weak type is part
    of the program) pass as they are.
    """
    import jax

    def _abstract(leaf):
        if isinstance(leaf, jax.ShapeDtypeStruct):
            return leaf
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            return leaf
        sharding = (leaf.sharding
                    if getattr(leaf, "committed", False) else None)
        return jax.ShapeDtypeStruct(
            tuple(shape), dtype, sharding=sharding,
            weak_type=bool(getattr(leaf, "weak_type", False)))

    def abstract_signature(args, kwargs=None) -> Any:
        return jax.tree_util.tree_map(_abstract, (args, kwargs or {}))

    return abstract_signature


#: ``memory_analysis()`` attribute -> key of ``program_analysis``'s
#: ``memory`` (``peak_memory_in_bytes`` only where the backend has one)
_MEMORY_FIELDS = (
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("alias_size_in_bytes", "alias_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
    ("generated_code_size_in_bytes", "code_bytes"),
)


def _resolve_program_analysis() -> Callable[..., Any]:
    """What XLA says of a jitted call's compiled program, from one
    ``lower().compile()``.

    Returns ``probe(jitted, args, kwargs) -> {"cost": dict | None,
    "memory": dict | None}``.  The arguments go through
    :func:`abstract_signature` (the concrete buffers may be donated and
    deleted by the time the compile ledger asks), so after the call has
    run the lowering and the executable come out of jax's caches and
    nothing is compiled.  ``cost`` is the ``cost_analysis`` dict
    (``{"flops": ..., "bytes accessed": ...}``), None when XLA reports
    no costs; ``memory`` the program's ``argument_bytes``,
    ``output_bytes``, ``alias_bytes``, ``temp_bytes``, ``code_bytes``,
    ``peak_bytes`` where the backend gives one, and ``reserved_bytes``
    (argument + output - alias + temp + code), None on a backend without
    the analysis.
    """
    abstract = __getattr__("abstract_signature")

    def _memory(compiled) -> Any:
        analyse = getattr(compiled, "memory_analysis", None)
        stats = analyse() if analyse is not None else None
        if stats is None:
            return None
        out = {key: int(getattr(stats, attr))
               for attr, key in _MEMORY_FIELDS}
        peak = getattr(stats, "peak_memory_in_bytes", None)
        if peak is not None:
            out["peak_bytes"] = int(peak)
        out["reserved_bytes"] = (
            out["argument_bytes"] + out["output_bytes"]
            - out["alias_bytes"] + out["temp_bytes"] + out["code_bytes"])
        return out

    def program_analysis(jitted, args, kwargs=None) -> Dict[str, Any]:
        a_args, a_kwargs = abstract(args, kwargs)
        compiled = jitted.lower(*a_args, **a_kwargs).compile()
        cost = compiled.cost_analysis()
        return {"cost": dict(cost) if cost else None,
                "memory": _memory(compiled)}

    return program_analysis


def _resolve_cost_analysis() -> Callable[..., Any]:
    """HLO cost accounting (FLOPs / bytes accessed) for a jitted call:
    ``probe(jitted, args, kwargs) -> dict | None``, the ``cost`` of
    :func:`program_analysis`."""
    analysis = __getattr__("program_analysis")

    def cost_analysis(jitted, args, kwargs=None) -> Any:
        return analysis(jitted, args, kwargs)["cost"]

    return cost_analysis


_RESOLVERS: Dict[str, Callable[[], Any]] = {
    "CompilerParams": _resolve_compiler_params,
    "abstract_signature": _resolve_abstract_signature,
    "axis_size": _resolve_axis_size,
    "cost_analysis": _resolve_cost_analysis,
    "pcast": _resolve_pcast,
    "program_analysis": _resolve_program_analysis,
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
