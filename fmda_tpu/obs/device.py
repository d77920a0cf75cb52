"""Device & compiler observability: compile ledger, cost/MFU, memory.

Every observability layer so far watches the *host* side of serving;
what XLA actually compiled, what each program costs, and what the
device is holding in memory were invisible.  This module closes that
gap with three host-resident instruments, all jax-free at import time
(jax is imported lazily, only on the paths that need a live runtime —
the module must be importable on router-role analysis hosts):

- :func:`tracked_jit` / :class:`TrackedFunction` — a drop-in wrapper
  over ``jax.jit`` that the hot jit sites (``SessionPool`` step,
  ``PredictorPool`` forward/gather) route through.  It detects each
  compile by watching the underlying jit cache size (the same private
  ``_cache_size`` probe the pools already used for their
  ``compile_count``, with a distinct-signature fallback), stamps it
  with a wall-clock duration and an abstract shape signature, and —
  once :meth:`TrackedFunction.mark_warm` has been called (after the
  precompile loop) — counts any further compile as an **unexpected
  recompile**: the recompile-storm failure mode promoted to a counted,
  alertable property (``[slo]`` ``recompile`` objective; the chaos and
  elastic soaks hard-gate ``recompiles_after_warmup == 0``).
- :class:`CompileLedger` — the process-wide record of every tracked
  program: compiles, calls, compile seconds **and what each compile was
  made of** (``trace_s``, ``lower_s``, ``backend_compile_s``, the
  persistent cache's ``hit`` / ``miss`` and retrieval time, from jax's
  own ``jax.monitoring`` events, by program name), what jax compiled
  outside any tracked call (the ``(untracked)`` table), and — when
  asked, never on a call — what the compiled program holds on the
  device (:meth:`TrackedFunction.memory`) and costs
  (FLOPs/bytes-accessed), both from one ``lower().compile()`` through
  :mod:`fmda_tpu.compat`.  Scrape
  time derives the arithmetic-intensity gauge and, for a device kind
  with a published peak (:data:`DEVICE_PEAKS`), ``device_mfu``; any
  other device gets no MFU gauge at all rather than an estimated one.
- :class:`DeviceMemoryMonitor` — a cadence-gated sampler over
  ``jax.live_arrays()`` (plus ``device.memory_stats()`` where the
  backend exposes it) with per-owner attribution (pools register a
  param/state tree callback), high-watermark tracking, and a
  monotonic-growth leak heuristic exported as a gauge the SLO engine
  alerts on.

Cost discipline: a :class:`TrackedFunction` whose ledger is disabled
is one attribute check + the underlying jit call — no allocation, no
lock.  The enabled steady-state path (no compile) is two cache-size
reads, one small lock window and one thread-local set and reset (which
tells jax's compile events inside a tracked call from those outside;
in the trainer's step loop it is part
of ``train_dispatch_us``, ``PERF.md`` §5; tests/test_device_obs.py holds
that the plane changes no output).  The ``jax.monitoring`` listeners
(registered once a process, at the first ``tracked_jit`` of an enabled
ledger) do constant work an event: tracing a decoder step fires one
event for every ``jnp`` call in it, and inside a tracked call every one
of those is a thread-local read and a string compare.  The analysis
probe (``cost_analysis`` in ``[profiling]``, and ``memory()``) lowers
from the call's own abstract signature, kept at the compile event — a
sharding on the committed leaves and only there — so after the call has
run it finds jax's cached lowering and executable and compiles nothing
(any other choice of shardings is a second lowering and a second
compile of the program, which is what the probe cost before PR 51).  It
still defaults OFF at module level and ON in ``[profiling]`` config: a
probe per compile is a tree-map and two cache lookups a program.

The ledger dump (:meth:`CompileLedger.dump`) has a pinned schema
(``LEDGER_SCHEMA`` / ``PROGRAM_SCHEMA``, ``LEDGER_SCHEMA_VERSION``)
— it is a flight-recorder bundle member and part of ``/device``, so its
keys are load-bearing for tooling and asserted in tests.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

#: bump when LEDGER_SCHEMA / PROGRAM_SCHEMA change shape
LEDGER_SCHEMA_VERSION = 2

#: exact key set of CompileLedger.dump() (pinned; bundle member)
LEDGER_SCHEMA = (
    "schema_version", "backend", "compiles_total",
    "compile_seconds_total", "unexpected_recompiles_total",
    "cost_probe_failures", "programs", "untracked",
)

#: exact key set of each dump()["programs"] entry (pinned).
#: ``compile_seconds`` is first-call wall time; ``trace_s`` + ``lower_s``
#: + ``backend_compile_s`` + ``rest_s`` sum to it (``rest_s``: the first
#: execution and the dispatch); ``cache`` is the persistent cache's
#: answer at the last compile (``"hit"`` / ``"miss"``, None where none
#: is configured); ``memory`` is :meth:`TrackedFunction.memory`'s dict
#: where someone has asked, else None.
PROGRAM_SCHEMA = (
    "program", "signature", "compiles", "calls", "compile_seconds",
    "unexpected", "flops", "bytes_accessed",
    "trace_s", "lower_s", "backend_compile_s", "rest_s", "cache",
    "cache_retrieval_s", "compile_time_saved_s", "memory",
)

#: what a compile is made of, in the order every parts vector here keeps
#: them (a tracked program's open compile, the ``(untracked)`` total,
#: the ledger's running totals)
COMPILE_PARTS = (
    "trace_s", "lower_s", "backend_compile_s", "cache_hits",
    "cache_misses", "cache_retrieval_s", "compile_time_saved_s",
)
_TRACE, _LOWER, _BACKEND, _HITS = 0, 1, 2, 3

#: jax.monitoring duration events -> index into a parts vector
_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": _TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": _LOWER,
    "/jax/core/compile/backend_compile_duration": _BACKEND,
    "/jax/compilation_cache/cache_retrieval_time_sec": 5,
    "/jax/compilation_cache/compile_time_saved_sec": 6,
}
#: jax.monitoring plain events -> index into a parts vector
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": 3,
    "/jax/compilation_cache/cache_misses": 4,
}

#: the ``(untracked)`` table keeps a name whose event took this long
UNTRACKED_NAME_MIN_S = 1e-3
#: ... and this many names; the rest fold into ``(other)``
UNTRACKED_NAMES = 32
#: compile records the ledger keeps (newest)
COMPILE_RECORDS = 256

#: Published per-chip peaks, keyed by jax ``device_kind``: (dense bf16
#: FLOP/s, HBM bytes/s).  Source: Google Cloud TPU documentation, "TPU
#: v5e" (197 TFLOP/s bf16, 819 GB/s).  The package's one table (the
#: benchmark keeps a copy of its own).  A kind that is not here has no peak:
#: utilization and roofline numbers are then absent (``None``), never estimated.
DEVICE_PEAKS: Dict[str, Tuple[float, float]] = {
    "TPU v5 lite": (197e12, 819e9),
}


def _log():
    import logging

    return logging.getLogger("fmda_tpu.obs")


def _leaf_signature(args: tuple, kwargs: dict) -> Tuple:
    """Abstract shape signature of a call: ``(shape, dtype)`` per
    array-like leaf (non-arrays fold in by repr of type + value where
    hashable).  Only computed on compile events / fallback counting —
    never on the per-call hot path when a cheap ``signature_of`` is
    supplied by the call site."""
    import jax

    sig = []
    for leaf in jax.tree_util.tree_leaves((args, kwargs)):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append((tuple(shape), str(dtype)))
        else:
            try:
                hash(leaf)
                sig.append(("py", repr(leaf)))
            except TypeError:  # noqa: BLE001 — loss-free: an unhashable
                # static arg still signs by type; nothing is dropped
                sig.append(("py", type(leaf).__name__))
    return tuple(sig)


# -- jax's own compile events ---------------------------------------------------
#
# jax 0.9.0 tells a ``jax.monitoring`` listener what each compile was made
# of, by name and on the compiling thread: ``jaxpr_trace_duration`` with
# ``fun_name="train_step"``, ``jaxpr_to_mlir_module_duration`` and
# ``backend_compile_duration`` with ``fun_name="jit(train_step)"``, and
# between the last two the persistent cache's ``cache_hits`` |
# ``cache_misses`` | ``cache_retrieval_time_sec`` |
# ``compile_time_saved_sec``.  The process has one pair of listeners,
# whatever its ledgers, registered at the first ``tracked_jit`` of an
# enabled one; they hand an event inside a tracked call to that function
# and every other event to the armed ledgers' ``(untracked)``.


class _Open(threading.local):
    """This thread's place in the compile events' stream."""

    #: the tracked function whose call is open on this thread
    fn: Optional["TrackedFunction"] = None
    #: the persistent cache's events since the last backend compile
    #: closed here: [hits, misses, retrieval_s, saved_s]
    cache: Optional[List[float]] = None
    #: ends and durations of the untracked trace events no later event
    #: has yet contained (see :func:`_uncounted`)
    ends: Optional[List[float]] = None
    durations: Optional[List[float]] = None


_OPEN = _Open()
_ARM_LOCK = threading.Lock()
_LISTENING = False
#: the ledgers that take the events outside tracked calls, weakly (a
#: tuple, replaced whole: the listener iterates it without a lock)
_ARMED: Tuple["weakref.ref[CompileLedger]", ...] = ()


def _armed() -> List["CompileLedger"]:
    return [ledger for ref in _ARMED if (ledger := ref()) is not None]


def _arm(ledger: "CompileLedger") -> None:
    """Give ``ledger`` the events outside tracked calls, and register the
    process's two listeners if this is the first ledger to ask."""
    global _LISTENING, _ARMED
    with _ARM_LOCK:
        live = _armed()
        if ledger not in live:
            live.append(ledger)
        _ARMED = tuple(weakref.ref(led) for led in live)
        if _LISTENING:
            return
        import jax

        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _LISTENING = True


def _on_event(event: str, **_kw) -> None:
    part = _CACHE_EVENTS.get(event)
    if part is None:
        return
    cache = _OPEN.cache
    if cache is None:
        cache = _OPEN.cache = [0, 0, 0.0, 0.0]
    cache[part - _HITS] += 1


def _on_duration(event: str, duration: float, fun_name: Optional[str] = None,
                 **_kw) -> None:
    part = _DURATION_EVENTS.get(event)
    if part is None:
        return
    if part > _BACKEND:  # the cache's two durations: pending, as its events
        cache = _OPEN.cache
        if cache is None:
            cache = _OPEN.cache = [0, 0, 0.0, 0.0]
        cache[part - _HITS] += duration
        return
    fn = _OPEN.fn
    if fn is not None:
        # inside a tracked call: the program's own three events are its
        # compile's parts; every other event is a ``jax.jit`` it calls,
        # whose time is in the program's own trace already
        if fun_name == (fn.name if part == _TRACE else fn._jit_name):
            cache = None
            if part == _BACKEND:
                cache, _OPEN.cache = _OPEN.cache, None
            fn._compile_part(part, duration, cache)
        elif part == _BACKEND:
            _OPEN.cache = None
        return
    cache = None
    if part == _BACKEND:
        cache, _OPEN.cache = _OPEN.cache, None
    counted = _uncounted(duration) if part == _TRACE else duration
    for ref in _ARMED:
        ledger = ref()
        if ledger is not None and ledger.enabled:
            ledger._untracked_part(part, duration, counted, fun_name, cache)


def _uncounted(duration: float) -> float:
    """The part of an untracked trace event that no earlier event on this
    thread has counted.  A traced function's event holds the events of
    the ``jax.jit``s it calls, and only ends are announced: an event that
    ends now and lasted ``duration`` contains every earlier one that
    ended after it began, so those are taken off it.  What is kept is the
    events nothing has contained yet, newest last (bounded: the oldest
    are final)."""
    now = time.perf_counter()
    began = now - duration
    ends, durations = _OPEN.ends, _OPEN.durations
    if ends is None:
        ends, durations = _OPEN.ends, _OPEN.durations = [], []
    inside = 0.0
    while ends and ends[-1] > began:
        ends.pop()
        inside += durations.pop()
    ends.append(now)
    durations.append(duration)
    if len(ends) > 512:
        del ends[:256], durations[:256]
    return max(0.0, duration - inside)


def _program_name(fun_name: Optional[str]) -> str:
    """``train_step`` of ``jit(train_step)``: one row a function in the
    ``(untracked)`` table, whichever of its three events names it."""
    if not fun_name:
        return "(unnamed)"
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _no_parts() -> List[float]:
    """A parts vector (:data:`COMPILE_PARTS`) that holds nothing yet."""
    return [0.0, 0.0, 0.0, 0, 0, 0.0, 0.0]


def _add_cache(parts: List[float], cache: Optional[List[float]]) -> None:
    """The persistent cache's pending ``[hits, misses, retrieval_s,
    saved_s]`` into a parts vector."""
    if cache is not None:
        for i, v in enumerate(cache):
            parts[_HITS + i] += v


def _split(parts: List[float], wall_s: float) -> Dict[str, object]:
    """What a program's record and a compile's record say of a parts
    vector beside its first-call wall time."""
    return {
        "trace_s": round(parts[_TRACE], 6),
        "lower_s": round(parts[_LOWER], 6),
        "backend_compile_s": round(parts[_BACKEND], 6),
        "rest_s": round(wall_s - sum(parts[:_HITS]), 6),
        "cache_retrieval_s": round(parts[5], 6),
        "compile_time_saved_s": round(parts[6], 6),
    }


class ProgramRecord:
    """Per-(program, signature) accounting inside a TrackedFunction."""

    __slots__ = ("signature", "compiles", "calls", "compile_s",
                 "unexpected", "flops", "bytes_accessed", "parts", "cache",
                 "abstract", "asked", "memory", "last_compile")

    def __init__(self, signature: object) -> None:
        self.signature = signature
        self.compiles = 0
        self.calls = 0
        self.compile_s = 0.0
        self.unexpected = 0
        self.flops = 0.0
        self.bytes_accessed = 0.0
        #: COMPILE_PARTS, summed over this record's compiles
        self.parts = _no_parts()
        #: the persistent cache's answer at the last compile
        self.cache: Optional[str] = None
        #: the compiling call's abstract ``(args, kwargs)``
        #: (``compat.abstract_signature``), kept for ``memory()``
        self.abstract: Optional[Tuple] = None
        #: whether anyone has asked what the program holds, and the
        #: answer (``memory()``)
        self.asked = False
        self.memory: Optional[Dict[str, object]] = None
        #: this record's newest entry in the ledger's compile ring
        self.last_compile: Optional[Dict[str, object]] = None


def _cache_answer(parts: List[float]) -> Optional[str]:
    if parts[_HITS]:
        return "hit"
    return "miss" if parts[_HITS + 1] else None


class TrackedFunction:
    """A jitted callable with compile accounting.

    Compile detection reads the underlying jit's private
    ``_cache_size`` hook before and after each call; a growth is a
    compile, attributed to this call's signature.  Under concurrent
    callers the *sum of observed deltas* equals the final cache size
    (each delta is claimed under the lock), so totals stay consistent
    — the thread-safety test pins exactly that.  On jax builds
    without the hook, distinct-signature counting is the fallback
    (the same degradation the pools' ``compile_count`` always had).

    The recorded "compile seconds" are first-call wall time — the
    operationally useful number for a serving host deciding whether
    precompile covered its buckets — and jax's own events for this
    program, fired on the calling thread while the call is open, split
    it: ``trace_s`` (the program's own trace event; the ``jax.jit``s it
    calls are in it, not added to it), ``lower_s``,
    ``backend_compile_s`` (a fetch from the persistent cache where
    ``cache`` reads ``"hit"``), and ``rest_s``, what is left: the first
    execution and the dispatch.

    At a compile event, never on a call, the function keeps the call's
    abstract signature; :meth:`memory` lowers from it when asked.
    """

    def __init__(
        self,
        jitted,
        *,
        name: str,
        ledger: "CompileLedger",
        signature_of: Optional[Callable[..., object]] = None,
    ) -> None:
        self.name = name
        self.ledger = ledger
        self._jit = jitted
        self._jit_name = f"jit({name})"
        self._signature_of = signature_of
        self._lock = threading.Lock()
        self._records: Dict[object, ProgramRecord] = {}
        self._seen_cache_size = 0
        self._fallback_sigs: set = set()
        self._warm = False
        self._unexpected = 0
        self._calls = 0
        #: COMPILE_PARTS of the compile open on each calling thread
        self._open_parts: Dict[int, List[float]] = {}

    # -- cache probe ---------------------------------------------------------

    def _raw_cache_size(self) -> Optional[int]:
        probe = getattr(self._jit, "_cache_size", None)
        if probe is None:
            return None
        try:
            return int(probe())
        except Exception:  # noqa: BLE001 — loss-free: a private-API
            # probe failing on some jax build must degrade to the
            # fallback counter, never break serving
            return None

    def cache_size(self) -> Optional[int]:
        """Compiled-program count from the jit cache, or None when the
        installed jax lacks the probe (callers fall back to their own
        distinct-shape counting, as the pools always did)."""
        return self._raw_cache_size()

    def _absorb_cache_size(self) -> None:
        """Fold the current cache size into the seen watermark without
        recording a compile — should the analysis probe's lowering miss
        jax's caches and grow this one, that growth must not read as a
        phantom compile."""
        raw = self._raw_cache_size()
        if raw is None:
            return
        with self._lock:
            if raw > self._seen_cache_size:
                self._seen_cache_size = raw

    # -- warmup --------------------------------------------------------------

    def mark_warm(self) -> None:
        """Declare warmup over: every compile from here on is
        *unexpected* (counted, evented, SLO-alertable)."""
        with self._lock:
            self._warm = True

    @property
    def warm(self) -> bool:
        with self._lock:
            return self._warm

    @property
    def unexpected_recompiles(self) -> int:
        with self._lock:
            return self._unexpected

    @property
    def calls(self) -> int:
        """Calls through an enabled ledger, compiling ones included."""
        with self._lock:
            return self._calls

    # -- the call path -------------------------------------------------------

    def _compile_part(self, part: int, duration: float,
                      cache: Optional[List[float]]) -> None:
        """One of jax's three events for this program, fired on the
        thread whose call is open (``_on_duration``); a backend compile
        brings the persistent cache's events that came before it."""
        ident = threading.get_ident()
        with self._lock:
            parts = self._open_parts.get(ident)
            if parts is None:
                parts = self._open_parts[ident] = _no_parts()
            parts[part] += duration
            _add_cache(parts, cache)
        self.ledger._add_totals(part, duration, cache)

    def __call__(self, *args, **kwargs):
        ledger = self.ledger
        if not ledger.enabled:
            return self._jit(*args, **kwargs)
        sig = (self._signature_of(*args, **kwargs)
               if self._signature_of is not None else None)
        with self._lock:
            before = self._seen_cache_size
        outer = _OPEN.fn
        _OPEN.fn = self
        t0 = time.perf_counter()
        try:
            out = self._jit(*args, **kwargs)
        finally:
            _OPEN.fn = outer
        dt = time.perf_counter() - t0
        after = self._raw_cache_size()
        compiled = False
        unexpected = False
        parts = None
        with self._lock:
            self._calls += 1
            if self._open_parts:
                parts = self._open_parts.pop(threading.get_ident(), None)
            if after is not None:
                if after > self._seen_cache_size:
                    compiled = True
                    self._seen_cache_size = after
            else:
                key = sig if sig is not None \
                    else _leaf_signature(args, kwargs)
                if key not in self._fallback_sigs:
                    self._fallback_sigs.add(key)
                    compiled = True
            if compiled and sig is None:
                sig = _leaf_signature(args, kwargs)
            rec = None
            if sig is not None:
                rec = self._records.get(sig)
                if rec is None:
                    rec = self._records[sig] = ProgramRecord(sig)
                rec.calls += 1
            if compiled:
                unexpected = self._warm
                if unexpected:
                    self._unexpected += 1
                if rec is not None:
                    rec.compiles += 1
                    rec.compile_s += dt
                    if unexpected:
                        rec.unexpected += 1
                    if parts is not None:
                        for i, v in enumerate(parts):
                            rec.parts[i] += v
                        rec.cache = _cache_answer(parts)
        if compiled:
            ledger._on_compile(self, rec, dt, parts, unexpected, args,
                               kwargs, cache_size_before=before)
        return out

    # -- what the program holds ----------------------------------------------

    def _record(self, signature: object = None) -> Optional[ProgramRecord]:
        """The record of ``signature``; without one, of the program with
        the most calls (the newest of equals: a function without a
        ``signature_of`` counts a record's compiling calls alone)."""
        with self._lock:
            if signature is not None:
                return self._records.get(signature)
            best = None
            for rec in self._records.values():
                if best is None or rec.calls >= best.calls:
                    best = rec
            return best

    def memory(self, signature: object = None
               ) -> Optional[Dict[str, object]]:
        """What the compiled program reserves on the device, by the
        compiler's own analysis: ``argument_bytes``, ``output_bytes``,
        ``alias_bytes``, ``temp_bytes``, ``code_bytes``, ``peak_bytes``
        where the backend gives one, ``reserved_bytes`` (argument +
        output - alias + temp + code), and ``asked`` (what asking took:
        seconds, and the lowerings and backend compiles jax announced
        meanwhile, which are 0 where the kept signature found the call's
        own executable).  None where the program has not compiled or the
        backend has no analysis.

        Lowers and compiles from the signature kept at the compile event,
        **when asked and once**: the answer is memoised on the record
        (the same object every time) and in the ledger's compile record.
        Never asked on a call; who may ask is in docs/observability.md
        "Compile ledger"."""
        rec = self._record(signature)
        if rec is None:
            return None
        if not rec.asked:
            self.ledger._analyse(self, rec)
        return rec.memory

    # -- export --------------------------------------------------------------

    def snapshot(self, *, ask_memory: bool = False
                 ) -> List[Dict[str, object]]:
        """Per-signature program records (PROGRAM_SCHEMA keys)."""
        with self._lock:
            records = list(self._records.items())
        out = []
        for sig, rec in records:
            if ask_memory and not rec.asked:
                self.ledger._analyse(self, rec)
            out.append({
                "program": self.name,
                "signature": repr(sig),
                "compiles": rec.compiles,
                "calls": rec.calls,
                "compile_seconds": round(rec.compile_s, 6),
                "unexpected": rec.unexpected,
                "flops": rec.flops,
                "bytes_accessed": rec.bytes_accessed,
                **_split(rec.parts, rec.compile_s),
                "cache": rec.cache,
                "memory": rec.memory,
            })
        return out

    def _totals(self) -> Tuple[int, float, int, float, float]:
        """(compiles, compile_s, unexpected, flops_done, bytes_done)."""
        with self._lock:
            records = list(self._records.values())
            unexpected = self._unexpected
        compiles = sum(r.compiles for r in records)
        compile_s = sum(r.compile_s for r in records)
        flops_done = sum(r.calls * r.flops for r in records)
        bytes_done = sum(r.calls * r.bytes_accessed for r in records)
        return compiles, compile_s, unexpected, flops_done, bytes_done


class CompileLedger:
    """Process-wide compile/cost accounting over tracked functions.

    Thread-safe; zero-cost when ``enabled`` is False (tracked calls
    skip straight to the jit).  Registration is *weak*: the owning
    pool/trainer holds the strong reference, and programs whose owner
    has been dropped leave the ledger with it — but not its compile
    records: the ledger keeps the newest :data:`COMPILE_RECORDS` of
    them itself (:meth:`compile_records`), with the program's
    ``memory`` where someone asked while the owner lived.  ``events`` is
    an optional :class:`fmda_tpu.obs.events.EventLog` attached by the
    Observability plane (latest instance wins, the chaos-hook
    discipline); each compile record is mirrored there as
    ``device.compile``.  Never into ``obs.events.default_epoch_log()``:
    that ring is the epoch account's alone, and its readers refuse a
    ring that holds anything else."""

    def __init__(self, *, enabled: bool = True,
                 cost_analysis: bool = False) -> None:
        self.enabled = enabled
        self.cost_analysis = cost_analysis
        self.events = None
        self._lock = threading.Lock()
        # weak registrations: the owner (pool, trainer) keeps the strong
        # reference; a dropped owner's programs fall off the ledger
        # instead of rooting the owner — and everything its jit closure
        # captures (device caches, parameter trees) — for process life
        self._functions: List["weakref.ref[TrackedFunction]"] = []
        self._backend: Optional[str] = None
        self._device_kind: Optional[str] = None
        self._cost_probe_failures = 0
        self._mfu_prev: Optional[Tuple[float, float, float]] = None
        self._mfu: Optional[float] = None
        self._intensity = 0.0
        self._reset_compile_account()

    def _reset_compile_account(self) -> None:
        with self._lock:
            #: COMPILE_PARTS, tracked and untracked together, ever
            self._totals = _no_parts()
            #: jax's trace / lowering / backend-compile events seen, ever
            self._events_seen = [0, 0, 0]
            #: COMPILE_PARTS of what compiled outside any tracked call
            self._untracked = _no_parts()
            #: name -> [trace_s, lower_s, backend_compile_s, events] of
            #: the untracked events of UNTRACKED_NAME_MIN_S or more
            self._untracked_names: Dict[str, List[float]] = {}
            self._compile_ring: deque = deque(maxlen=COMPILE_RECORDS)

    # -- registration --------------------------------------------------------

    def track(self, fn: TrackedFunction) -> None:
        with self._lock:
            self._functions.append(weakref.ref(fn))
        if self.enabled:
            _arm(self)

    def functions(self) -> List[TrackedFunction]:
        with self._lock:
            live = [(ref, fn) for ref in self._functions
                    if (fn := ref()) is not None]
            if len(live) != len(self._functions):
                self._functions = [ref for ref, _ in live]
            return [fn for _, fn in live]

    def mark_warm(self) -> None:
        for fn in self.functions():
            fn.mark_warm()

    def reset(self) -> None:
        """Drop every tracked function and derived state (test
        isolation only — live pools keep their own references)."""
        with self._lock:
            self._functions = []
            self._backend = None
            self._device_kind = None
            self._cost_probe_failures = 0
            self._mfu_prev = None
            self._mfu = None
            self._intensity = 0.0
        self._reset_compile_account()

    # -- compile events ------------------------------------------------------

    def backend(self) -> str:
        return self._device()[0]

    def _device(self) -> Tuple[str, Optional[str]]:
        """(platform, device_kind) of the default device, read once."""
        with self._lock:
            if self._backend is not None:
                return self._backend, self._device_kind
        name, kind = "unknown", None
        try:
            import jax

            dev = jax.devices()[0]
            name, kind = str(dev.platform), str(dev.device_kind)
        except Exception:  # noqa: BLE001 — loss-free: a jax-free or
            # broken-runtime host still gets a ledger, just without a
            # device name (and therefore without an MFU gauge)
            pass
        with self._lock:
            self._backend, self._device_kind = name, kind
        return name, kind

    def _add_totals(self, part: int, duration: float,
                    cache: Optional[List[float]]) -> None:
        with self._lock:
            self._totals[part] += duration
            self._events_seen[part] += 1
            _add_cache(self._totals, cache)

    def _untracked_part(self, part: int, duration: float, counted: float,
                        fun_name: Optional[str],
                        cache: Optional[List[float]]) -> None:
        """One of jax's events outside any tracked call (``_on_duration``):
        ``counted`` of its ``duration`` is new to this thread's total (a
        trace event holds the events of what it calls); the table's rows
        keep each name's own events whole."""
        with self._lock:
            self._totals[part] += counted
            self._events_seen[part] += 1
            self._untracked[part] += counted
            _add_cache(self._totals, cache)
            _add_cache(self._untracked, cache)
            if duration < UNTRACKED_NAME_MIN_S:
                return
            names = self._untracked_names
            name = _program_name(fun_name)
            row = names.get(name)
            if row is None:
                if len(names) >= UNTRACKED_NAMES:
                    name = "(other)"
                    row = names.get(name)
                if row is None:
                    row = names[name] = [0.0, 0.0, 0.0, 0]
            row[part] += duration
            row[3] += 1

    def compile_parts_total(self) -> Tuple[float, float, float, int, int,
                                           float]:
        """``(trace_s, lower_s, backend_compile_s, cache_hits,
        cache_misses, cache_retrieval_s)`` of everything jax compiled in
        this process since the ledger was armed, tracked and untracked
        together: six reads, for a caller that accounts an interval by
        difference (``Trainer._end_epoch``)."""
        with self._lock:
            t = self._totals
            return t[0], t[1], t[2], t[3], t[4], t[5]

    def _compile_events_seen(self) -> List[int]:
        """jax's trace / lowering / backend-compile events so far."""
        with self._lock:
            return list(self._events_seen)

    def compile_records(self) -> List[Dict[str, object]]:
        """The newest :data:`COMPILE_RECORDS` compiles of tracked
        programs, oldest first: ``program``, ``signature``, ``compile_s``
        and its ``trace_s`` / ``lower_s`` / ``backend_compile_s`` /
        ``rest_s``, ``cache``, ``cache_retrieval_s``,
        ``compile_time_saved_s``, ``backend``, ``unexpected``,
        ``cache_size_before``, and ``memory`` (None until someone asks
        the program).  They outlive the function they describe."""
        with self._lock:
            return list(self._compile_ring)

    def untracked(self) -> Dict[str, object]:
        """What jax compiled outside any tracked call: COMPILE_PARTS in
        total (every instant once) and, by name, the events of 1 ms or
        more (each name's own events whole, so a row holds the rows of
        what it calls; :data:`UNTRACKED_NAMES` names, the rest under
        ``(other)``)."""
        with self._lock:
            total = dict(zip(COMPILE_PARTS, self._untracked))
            rows = {name: list(row)
                    for name, row in self._untracked_names.items()}
        for key in ("trace_s", "lower_s", "backend_compile_s",
                    "cache_retrieval_s", "compile_time_saved_s"):
            total[key] = round(total[key], 6)
        total["by_name"] = {
            name: {"trace_s": round(row[0], 6), "lower_s": round(row[1], 6),
                   "backend_compile_s": round(row[2], 6),
                   "events": int(row[3])}
            for name, row in sorted(rows.items())}
        return total

    def _on_compile(self, fn: TrackedFunction, rec: ProgramRecord,
                    dt: float, parts: Optional[List[float]],
                    unexpected: bool, args: tuple, kwargs: dict, *,
                    cache_size_before: int) -> None:
        backend = self.backend()
        try:
            from fmda_tpu import compat

            rec.abstract = compat.abstract_signature(args, kwargs)
        except Exception:  # noqa: BLE001 — loss-free: a call whose
            # leaves cannot be described leaves ``memory()`` None
            rec.abstract = None
        # what was asked of the program before this compile is not this
        # one's answer
        rec.asked, rec.memory = False, None
        if self.cost_analysis:
            self._analyse(fn, rec)
        parts = parts or _no_parts()
        record: Dict[str, object] = {
            "program": fn.name,
            "signature": repr(rec.signature),
            "compile_s": round(dt, 6),
            **_split(parts, dt),
            "cache": _cache_answer(parts),
            "backend": backend,
            "unexpected": bool(unexpected),
            "cache_size_before": cache_size_before,
        }
        events = self.events
        if events is not None:
            events.emit("device.compile", **record)
            if unexpected:
                events.emit(
                    "device.unexpected_recompile",
                    program=fn.name,
                    signature=repr(rec.signature),
                    backend=backend,
                )
        record["ts"] = time.time()
        record["memory"] = rec.memory
        rec.last_compile = record
        with self._lock:
            self._compile_ring.append(record)

    def _analyse(self, fn: TrackedFunction, rec: ProgramRecord) -> None:
        """One ``lower().compile()`` from the record's kept signature:
        the program's memory, and its cost where ``cost_analysis`` is
        on.  After the call has run it finds jax's own lowering and
        executable, so it compiles nothing; what it took is in the
        answer's ``asked``."""
        memory = cost = None
        if rec.abstract is not None:
            seen = self._compile_events_seen()
            t0 = time.perf_counter()
            try:
                from fmda_tpu import compat

                got = compat.program_analysis(fn._jit, *rec.abstract)
                memory, cost = got["memory"], got["cost"]
            except Exception:  # noqa: BLE001 — loss-free: the probe is
                # best-effort telemetry over private-ish jax surface; a
                # failure is counted below, never raised into serving
                pass
            if memory is not None:
                now = self._compile_events_seen()
                memory["asked"] = {
                    "s": round(time.perf_counter() - t0, 6),
                    "lowerings": now[_LOWER] - seen[_LOWER],
                    "backend_compiles": now[_BACKEND] - seen[_BACKEND]}
        if memory is None and cost is None:
            with self._lock:
                self._cost_probe_failures += 1
        with fn._lock:
            if not rec.asked:
                rec.asked, rec.memory = True, memory
                if rec.last_compile is not None:
                    rec.last_compile["memory"] = memory
            if cost is not None and self.cost_analysis:
                rec.flops = float(cost.get("flops", 0.0) or 0.0)
                rec.bytes_accessed = float(
                    cost.get("bytes accessed", 0.0) or 0.0)
        # a lowering that missed jax's caches can grow the jit cache;
        # absorb so the next call does not read it as a phantom compile
        fn._absorb_cache_size()

    # -- derived totals ------------------------------------------------------

    @property
    def recompiles_after_warmup(self) -> int:
        return sum(f.unexpected_recompiles for f in self.functions())

    @property
    def compiles_total(self) -> int:
        return sum(f._totals()[0] for f in self.functions())

    @property
    def compile_seconds_total(self) -> float:
        return sum(f._totals()[1] for f in self.functions())

    def flops_done(self) -> float:
        return sum(f._totals()[3] for f in self.functions())

    def mfu(self) -> Optional[float]:
        """Last scrape-interval MFU; ``None`` until two scrapes land, and
        always ``None`` on a device kind without a published peak."""
        with self._lock:
            return self._mfu

    # -- export --------------------------------------------------------------

    def dump(self, *, ask_memory: bool = False) -> Dict[str, object]:
        """The pinned-schema ledger document (LEDGER_SCHEMA keys;
        ``/device`` + flight-recorder bundle member).  With
        ``ask_memory`` every live program that has not been asked what
        it holds is asked now (:meth:`TrackedFunction.memory`)."""
        functions = self.functions()
        programs: List[Dict[str, object]] = []
        for fn in functions:
            programs.extend(fn.snapshot(ask_memory=ask_memory))
        programs.sort(key=lambda p: (p["program"], p["signature"]))
        compiles = sum(p["compiles"] for p in programs)
        compile_s = sum(p["compile_seconds"] for p in programs)
        unexpected = sum(f.unexpected_recompiles for f in functions)
        with self._lock:
            failures = self._cost_probe_failures
            backend = self._backend
        return {
            "schema_version": LEDGER_SCHEMA_VERSION,
            "backend": backend,
            "compiles_total": compiles,
            "compile_seconds_total": round(compile_s, 6),
            "unexpected_recompiles_total": unexpected,
            "cost_probe_failures": failures,
            "programs": programs,
            "untracked": self.untracked(),
        }

    def families(self) -> Dict[str, List[Dict[str, object]]]:
        """Scrape-time collector (registry snapshot shape): compile
        counters per program, cost gauges, and the MFU/intensity
        roofline position derived from inter-scrape FLOP deltas."""
        counters: List[Dict[str, object]] = []
        gauges: List[Dict[str, object]] = []
        flops_done = 0.0
        bytes_done = 0.0
        # aggregate by program name: several pools in one process (a
        # multi-worker soak) can track same-named programs, and the
        # exposition must stay one sample per label set
        by_program: Dict[str, List[float]] = {}
        for fn in self.functions():
            compiles, compile_s, unexpected, f_done, b_done = fn._totals()
            flops_done += f_done
            bytes_done += b_done
            size = fn.cache_size()
            cached = float(len(fn.snapshot()) if size is None else size)
            acc = by_program.setdefault(fn.name, [0.0, 0.0, 0.0, 0.0])
            acc[0] += compiles
            acc[1] += compile_s
            acc[2] += unexpected
            acc[3] += cached
        for name, (compiles, compile_s, unexpected, cached) \
                in sorted(by_program.items()):
            counters.append({
                "name": "compile_total",
                "labels": {"program": name},
                "value": int(compiles),
            })
            counters.append({
                "name": "compile_seconds_total",
                "labels": {"program": name},
                "value": compile_s,
            })
            counters.append({
                "name": "compile_unexpected_total",
                "labels": {"program": name},
                "value": int(unexpected),
            })
            gauges.append({
                "name": "compile_cached_programs",
                "labels": {"program": name},
                "value": cached,
            })
        with self._lock:
            counters.append({
                "name": "compile_cost_probe_failures_total",
                "labels": {},
                "value": self._cost_probe_failures,
            })
        backend, device_kind = self._device()
        peaks = DEVICE_PEAKS.get(device_kind)
        now = time.monotonic()
        with self._lock:
            prev = self._mfu_prev
            self._mfu_prev = (now, flops_done, bytes_done)
            if prev is not None and now > prev[0]:
                elapsed = now - prev[0]
                d_flops = max(0.0, flops_done - prev[1])
                d_bytes = max(0.0, bytes_done - prev[2])
                if peaks is not None:
                    self._mfu = d_flops / elapsed / peaks[0]
                self._intensity = (d_flops / d_bytes) if d_bytes else 0.0
            mfu, intensity = self._mfu, self._intensity
        if mfu is not None:
            gauges.append({
                "name": "device_mfu",
                "labels": {"backend": backend, "device_kind": device_kind},
                "value": mfu,
            })
        gauges.append({
            "name": "device_arithmetic_intensity",
            "labels": {"backend": backend},
            "value": intensity,
        })
        # the cell-seam kernel-fallback counters (ops/dispatch) join
        # the device vocabulary here: no family silently serves the
        # reference path without a scrape noticing
        try:
            from fmda_tpu.ops.dispatch import kernel_fallbacks

            for key, n in sorted(kernel_fallbacks().items()):
                cell, _, reason = key.partition(":")
                counters.append({
                    "name": "device_kernel_fallback_total",
                    "labels": {"cell": cell, "reason": reason},
                    "value": n,
                })
        except Exception:  # noqa: BLE001 — loss-free: the dispatch
            # seam is optional telemetry; a broken import must not
            # take the scrape down
            _log().warning("kernel-fallback scrape failed", exc_info=True)
        return {"counters": counters, "gauges": gauges}


class DeviceMemoryMonitor:
    """Cadence-gated device/live-array memory sampler.

    Owners (pools) register a callback returning their live pytree;
    each sample attributes leaf ``nbytes`` by owner, sums the whole
    process's ``jax.live_arrays()``, folds in the backend's
    ``memory_stats()`` where exposed, tracks the high watermark, and
    runs a monotonic-growth leak heuristic: ``leak_window``
    consecutive samples each strictly above the last → suspected leak
    (a gauge the SLO engine alerts on).  ``maybe_sample`` costs one
    clock read when not due — safe to call per hot-loop step."""

    def __init__(self, *, interval_s: float = 5.0,
                 leak_window: int = 12, enabled: bool = True) -> None:
        self.enabled = enabled
        self.interval_s = interval_s
        self.leak_window = max(3, int(leak_window))
        self._lock = threading.Lock()
        self._owners: Dict[str, Callable[[], object]] = {}
        self._next_due = 0.0
        self._by_owner: Dict[str, float] = {}
        self._live_bytes = 0.0
        self._device_bytes = 0.0
        self._watermark = 0.0
        self._history: deque = deque(maxlen=self.leak_window)
        self._leak = False
        self._samples = 0

    def register_owner(self, name: str,
                       tree_fn: Callable[[], object]) -> None:
        """Attach an owner's live-tree callback (same-name
        re-registration replaces — pools rebuild across migrations)."""
        with self._lock:
            self._owners[name] = tree_fn

    def maybe_sample(self, now: Optional[float] = None) -> bool:
        """Sample if the cadence is due.  Returns True when a sample
        was taken."""
        if not self.enabled:
            return False
        if now is None:
            now = time.monotonic()
        if now < self._next_due:
            return False
        self._next_due = now + self.interval_s
        self.sample()
        return True

    @staticmethod
    def _tree_bytes(tree: object) -> float:
        import jax

        total = 0.0
        for leaf in jax.tree_util.tree_leaves(tree):
            total += float(getattr(leaf, "nbytes", 0) or 0)
        return total

    def sample(self) -> Dict[str, object]:
        """Take one sample now (cadence ignored)."""
        live = 0.0
        device_bytes = 0.0
        by_owner: Dict[str, float] = {}
        with self._lock:
            owners = dict(self._owners)
        try:
            import jax

            live = sum(float(getattr(a, "nbytes", 0) or 0)
                       for a in jax.live_arrays())
            for name, tree_fn in owners.items():
                try:
                    by_owner[name] = self._tree_bytes(tree_fn())
                except Exception:  # noqa: BLE001 — loss-free: a
                    # mid-teardown owner (migrating pool) reads as
                    # zero for one sample, never breaks the monitor
                    by_owner[name] = 0.0
            try:
                stats = jax.local_devices()[0].memory_stats()
                if stats:
                    device_bytes = float(stats.get("bytes_in_use", 0.0))
            except Exception:  # noqa: BLE001 — loss-free: CPU/older
                # backends expose no memory_stats; live_arrays is the
                # signal there
                device_bytes = 0.0
        except Exception:  # noqa: BLE001 — loss-free: a jax-free host
            # keeps an (empty) monitor rather than crashing telemetry
            pass
        with self._lock:
            self._live_bytes = live
            self._device_bytes = device_bytes
            self._by_owner = by_owner
            basis = max(live, device_bytes)
            if basis > self._watermark:
                self._watermark = basis
            self._history.append(basis)
            self._leak = (
                len(self._history) == self.leak_window
                and all(b > a for a, b in zip(self._history,
                                              list(self._history)[1:]))
            )
            self._samples += 1
            return self.doc_locked()

    # -- export --------------------------------------------------------------

    def doc_locked(self) -> Dict[str, object]:
        return {
            "live_bytes": self._live_bytes,
            "device_bytes_in_use": self._device_bytes,
            "by_owner": dict(self._by_owner),
            "watermark_bytes": self._watermark,
            "leak_suspected": self._leak,
            "samples": self._samples,
            "leak_window": self.leak_window,
        }

    def doc(self) -> Dict[str, object]:
        with self._lock:
            return self.doc_locked()

    @property
    def watermark_bytes(self) -> float:
        with self._lock:
            return self._watermark

    @property
    def live_bytes(self) -> float:
        with self._lock:
            return self._live_bytes

    @property
    def leak_suspected(self) -> bool:
        with self._lock:
            return self._leak

    def families(self) -> Dict[str, List[Dict[str, object]]]:
        with self._lock:
            by_owner = dict(self._by_owner)
            live = self._live_bytes
            watermark = self._watermark
            leak = self._leak
            samples = self._samples
        gauges = [{
            "name": "device_live_bytes",
            "labels": {"owner": "process"},
            "value": live,
        }]
        for name, nbytes in sorted(by_owner.items()):
            gauges.append({
                "name": "device_live_bytes",
                "labels": {"owner": name},
                "value": nbytes,
            })
        gauges.append({
            "name": "device_memory_watermark_bytes",
            "labels": {},
            "value": watermark,
        })
        gauges.append({
            "name": "device_memory_leak_suspected",
            "labels": {},
            "value": 1.0 if leak else 0.0,
        })
        counters = [{
            "name": "device_memory_samples_total",
            "labels": {},
            "value": samples,
        }]
        return {"counters": counters, "gauges": gauges}


# -- the factory --------------------------------------------------------------


def tracked_jit(fn, *, name: str,
                ledger: Optional[CompileLedger] = None,
                signature_of: Optional[Callable[..., object]] = None,
                **jit_kwargs) -> TrackedFunction:
    """``jax.jit`` with compile accounting: the tracked-jit seam every
    hot jit site in ``runtime/`` routes through (enforced by the
    ``tracked-jit`` lint rule).

    ``signature_of(*args, **kwargs)`` is the cheap per-call program
    signature (the pools pass the padded batch size); without it the
    signature is derived from leaf shapes, but only on compile events
    — the steady-state path never tree-flattens.  ``jit_kwargs`` pass
    straight through (``donate_argnums``, shardings, ...).

    ``fn`` takes ``name`` as its ``__name__`` before it is jitted, so
    that a device profile's ``XLA Modules`` line reads ``jit_<name>``:
    one name in the trace, the compile ledger and the docs."""
    import jax

    fn.__name__ = name
    if ledger is None:
        ledger = default_ledger()
    tracked = TrackedFunction(
        jax.jit(fn, **jit_kwargs),
        name=name, ledger=ledger, signature_of=signature_of)
    ledger.track(tracked)
    return tracked


# -- process defaults + config ------------------------------------------------

_DEFAULT_LEDGER = CompileLedger(enabled=True, cost_analysis=False)
_DEFAULT_MEMORY = DeviceMemoryMonitor()


def default_ledger() -> CompileLedger:
    return _DEFAULT_LEDGER


def default_memory_monitor() -> DeviceMemoryMonitor:
    return _DEFAULT_MEMORY


def configure_device_obs(cfg) -> None:
    """Apply a ``ProfilingConfig`` to the process defaults (serve-time
    entry points call this before building pools)."""
    led = default_ledger()
    led.enabled = bool(cfg.enabled)
    led.cost_analysis = bool(cfg.cost_analysis)
    if led.enabled:
        _arm(led)
    mon = default_memory_monitor()
    mon.enabled = bool(cfg.enabled)
    mon.interval_s = float(cfg.memory_interval_s)
    window = max(3, int(cfg.memory_leak_window))
    if window != mon.leak_window:
        mon.leak_window = window
        mon._history = deque(mon._history, maxlen=window)
    # the host profiler is a serve-time opt-in: daemons that set
    # [profiling] host_profiler get the continuous sampler; everything
    # else keeps the profiler importable-but-idle (tests drive
    # sample_once directly)
    from fmda_tpu.obs.pyprof import default_profiler

    prof = default_profiler()
    prof.interval_ms = float(cfg.profile_interval_ms)
    prof.max_stacks = int(cfg.profile_max_stacks)
    if cfg.enabled and cfg.host_profiler:
        prof.start()
    elif prof.running:
        prof.stop()


def device_report(*, ledger: Optional[CompileLedger] = None,
                  memory: Optional[DeviceMemoryMonitor] = None
                  ) -> Dict[str, object]:
    """The ``/device`` endpoint / flight-recorder ``device.json``
    document: ledger dump (each live program asked what it holds, once)
    + memory doc + raw kernel-fallback map."""
    ledger = ledger if ledger is not None else default_ledger()
    memory = memory if memory is not None else default_memory_monitor()
    try:
        from fmda_tpu.ops.dispatch import kernel_fallbacks

        fallbacks = kernel_fallbacks()
    except Exception:  # noqa: BLE001 — loss-free: optional seam, see
        # families(); an import failure reads as an empty map
        fallbacks = {}
    return {
        "ledger": ledger.dump(ask_memory=True),
        "memory": memory.doc(),
        "kernel_fallbacks": fallbacks,
        "recompiles_after_warmup": ledger.recompiles_after_warmup,
        "mfu": ledger.mfu(),
    }
