"""Contract observation hooks: how the static analyzer sees the atomics API.

A jaxpr records *primitives*, not API calls — by the time `execute` has
dispatched, the trace contains scatters and collectives with no marker
saying "this one went through the sanctioned front-end" or "this table
declared axis='model'".  This module is that marker: the atomics entry
points (`execute`, `execute_until`, `AtomicTable.__init__`) call
:func:`notify` with their call-site contract (table, op, tier arguments),
and an installed observer — `repro.analysis` during a `check()` trace —
records them alongside the jaxpr variables the arguments trace to.

Cost discipline (same pattern as `repro.telemetry`): the hot-path guard is
one module-global (``_observer is None``), so production dispatch pays a
single attribute read per call when no analysis is running.  Observer
exceptions are swallowed into :data:`_errors` — observation must never
change what the observed code does — and the analysis session surfaces
them as findings instead of crashing the trace.
"""

from __future__ import annotations

import contextlib
import traceback
from typing import Any, Callable, Dict, List, Optional, Tuple

#: the one hot-path guard; installed by :func:`observe`
_observer: Optional[Callable[[str, Dict[str, Any]], None]] = None

#: exceptions raised *by the observer* (never propagated into dispatch);
#: drained by the analysis session at the end of a trace
_errors: List[str] = []

#: path fragments naming the sanctioned RMW implementation modules — a
#: scatter whose source frames include one of these came from the engine
#: itself, not from user code bypassing it.  `repro.analysis.rules` is the
#: consumer; the list lives here because it IS the contract ("these modules
#: may touch table memory directly").
SANCTIONED_PATHS: Tuple[str, ...] = (
    "repro/core/rmw",          # rmw.py, rmw_engine.py, rmw_sharded.py
    "repro/atomics/",          # front-end, retry, reshard internals
    "repro/kernels/rmw",       # the Pallas kernel
)


def active() -> bool:
    """True while an analysis observer is installed."""
    return _observer is not None


def notify(site: str, **fields) -> None:
    """Report one contract event to the installed observer (if any).

    ``site`` ∈ {"table", "execute", "execute_until"}; ``fields`` carry the
    live API objects (the observer reads tracer→var mappings off them at
    trace time).  Never raises, never mutates its arguments.
    """
    cb = _observer
    if cb is None:
        return
    try:
        cb(site, fields)
    except Exception:  # noqa: BLE001 — observation must not break dispatch
        _errors.append(traceback.format_exc())


@contextlib.contextmanager
def observe(callback: Callable[[str, Dict[str, Any]], None]):
    """Install ``callback`` as the contract observer for the scope; yields
    the list collecting observer-side errors (drained on exit)."""
    global _observer, _site_counter
    prev = _observer
    _observer = callback
    _site_counter = 0
    _errors.clear()
    try:
        yield _errors
    finally:
        _observer = prev


#: name of the identity primitive :func:`mark` injects — the bridge between
#: an API-level observation ("this array is an AtomicTable's data", "these
#: are a Cas batch's operands") and the jaxpr the analyzer walks afterwards.
#: Trace-internal `Var` objects do NOT survive jax's literal-inlining clone
#: pass, so tagging lineage *in the dataflow itself* is the only identity
#: that reaches the final jaxpr.
MARKER = "atomics_lint_marker"

_marker_p = None
_site_counter = 0


def _get_marker():
    global _marker_p
    if _marker_p is None:
        from jax.extend.core import Primitive
        from jax.interpreters import ad, batching, mlir

        p = Primitive(MARKER)
        p.def_impl(lambda x, **_: x)
        p.def_abstract_eval(lambda x, **_: x)
        # identity is linear: one rule covers both jvp and transpose, so
        # marked arrays pass through grad/vmap untouched
        ad.deflinear2(p, lambda ct, x, **kw: [ct])
        batching.defvectorized(p)
        try:
            mlir.register_lowering(p, lambda ctx, x, **kw: [x])
        except Exception:  # noqa: BLE001 — lowering never needed for trace
            pass
        _marker_p = p
    return _marker_p


def next_site() -> int:
    """Fresh id tying an `execute` observation to its marker equations."""
    global _site_counter
    _site_counter += 1
    return _site_counter


def mark(x, role: str, **params):
    """Pass ``x`` through the identity marker primitive (observer active
    only; no-op otherwise).  The resulting jaxpr equation carries ``role``
    (+ ``params``) so the rule engine identifies the array structurally —
    on concrete values the identity impl runs eagerly and nothing is
    recorded, which is exactly right: a constant is not trace dataflow."""
    if _observer is None or x is None:
        return x
    try:
        return _get_marker().bind(x, role=role, **params)
    except Exception:  # noqa: BLE001 — marking must never break dispatch
        _errors.append(traceback.format_exc())
        return x


def caller_site(skip: Tuple[str, ...] = ("repro/atomics/",
                                         "repro/analysis/",
                                         "/jax/", "/jax_", "jax/_src")
                ) -> Tuple[Optional[str], Optional[int]]:
    """(file, line) of the innermost stack frame outside the atomics /
    analysis / jax machinery — the user call site a finding should point
    at.  Best-effort: (None, None) when every frame is machinery."""
    for fr in reversed(traceback.extract_stack()):
        fname = fr.filename.replace("\\", "/")
        if any(s in fname for s in skip):
            continue
        if fname.startswith("<"):          # <string>, <stdin>
            continue
        return fr.filename, fr.lineno
    return None, None
