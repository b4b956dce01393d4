"""The rule engine: walk a jaxpr (recursively) and apply checkers A001-A005.

Rules
=====

A001 (error)   race detector — a scatter-family primitive writing an
               AtomicTable-lineage buffer without coming from a sanctioned
               RMW module (`contracts.SANCTIONED_PATHS`), or a
               duplicate-capable set-style scatter / a multiply-scattered
               plain buffer with potentially-aliasing indices.  XLA leaves
               duplicate-index scatter-set ordering undefined; table writes
               additionally bypass the serialized-equivalence contract.
A002 (warn)    primitive strength — a `Cas` whose update value is
               ``expected + d`` / ``max(expected, x)`` / ``min`` /
               ``expected`` itself is expressible as Faa/Max/Min/a read:
               consensus number 2 beats ∞ when 2 is all you need
               (arxiv 1802.03844; `AtomicOp.CONSENSUS_NUMBER`).
A003 (warn)    unbounded retry — a `while_loop` whose body issues a CAS and
               whose continuation predicate depends on *no* counter-like
               carry: the trip count is purely data-dependent (the CAS-storm
               shape of arxiv 1305.5800).  `atomics.execute_until` is the
               bounded, policy-driven spelling.
A004 (error)   donation safety — a jitted call that donates an input buffer
               which a *later* equation (or the function result) still
               reads: the donated buffer may already be aliased to the
               output.  The API-level half (donating step functions handed
               to recovery without a state factory) lives in
               `analysis.check_recovery`.
A005 (error)   shard contract — an `execute` on a mesh-sharded table whose
               declared axes are not bound (outside ``shard_map``), or
               mixed ``reverse_ranks`` directions over one combine tree
               whose forward pass never fetched (no pre-image feedback, so
               the reversed stream cannot be a revert).

Everything here is pure jaxpr walking + the `trace.TraceResult` side
channel; no execution.
"""

from __future__ import annotations

import os
import sysconfig
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import jaxlib
import numpy as np
from jax.extend.core import Literal, Var

from repro.atomics import contracts
from repro.atomics.ops import OP_KINDS
from repro.analysis.findings import Finding, make_finding
from repro.analysis.trace import CallSite, TraceResult

#: frames under these directories are JAX's own, never the user code a
#: finding should point at
_JAX_DIRS = tuple(os.path.dirname(m.__file__) + os.sep for m in (jax, jaxlib))
_STDLIB_DIR = sysconfig.get_paths()["stdlib"] + os.sep
#: installed packages (this one too, when it is not an editable install)
#: sit under the stdlib prefix on a standard layout: user code all the same
_PACKAGE_PARTS = (os.sep + "site-packages" + os.sep,
                  os.sep + "dist-packages" + os.sep)


def _is_machinery(file_name: str) -> bool:
    """True for a frame in JAX or in the standard library proper."""
    if file_name.startswith(_JAX_DIRS):
        return True
    return (file_name.startswith(_STDLIB_DIR)
            and not any(p in file_name for p in _PACKAGE_PARTS))


#: scatter-family primitive names (set-style "scatter" is the
#: undefined-ordering one; add/mul/min/max are duplicate-commutative)
SCATTER_PRIMS = ("scatter", "scatter-add", "scatter-mul", "scatter-min",
                 "scatter-max")

#: shape-preserving wrappers resolved through when chasing a value to the
#: equation that actually computes it (the contracts marker is an identity)
_TRANSPARENT = ("convert_element_type", "broadcast_in_dim", "reshape",
                "squeeze", "expand_dims", "copy", "stop_gradient",
                "transpose", contracts.MARKER)


def _frames(eqn) -> List[Any]:
    """The equation's user frames, innermost first (JAX and stdlib frames
    dropped)."""
    si = getattr(eqn, "source_info", None)
    tb = getattr(si, "traceback", None)
    if tb is None:
        return []
    return [fr for fr in tb.frames if not _is_machinery(fr.file_name)]


def _sanctioned(eqn) -> bool:
    """True when any user frame of the equation lives in a sanctioned RMW
    module — the scatter is the engine's own, not a bypass."""
    for fr in _frames(eqn):
        fname = fr.file_name.replace("\\", "/")
        if any(p in fname for p in contracts.SANCTIONED_PATHS):
            return True
    return False


def _loc(eqn) -> Tuple[Optional[str], Optional[int]]:
    frames = _frames(eqn)
    if not frames:
        return None, None
    fr = frames[0]                       # innermost user frame
    return fr.file_name, fr.line_num


class _Ctx:
    """Mutable state threaded through the recursive walk."""

    def __init__(self, tr: TraceResult):
        self.tr = tr
        self.findings: List[Finding] = []
        self.table_vars: Set[Var] = set(tr.table_invars)
        self.defs: Dict[Var, Any] = {}            # var -> defining eqn
        self.const_vals: Dict[Var, Any] = {}      # constvar -> concrete
        self._roots: Dict[Var, Var] = {}          # buffer lineage union
        self.root_writes: Dict[Var, List[Any]] = {}
        self.site_map = {cs.site_id: cs for cs in tr.callsites
                         if cs.site_id is not None}
        self._cas_cache: Dict[int, bool] = {}

    def root(self, v):
        seen = []
        while v in self._roots and self._roots[v] is not v:
            seen.append(v)
            v = self._roots[v]
        for s in seen:
            self._roots[s] = v
        return v

    def link(self, child: Var, parent) -> None:
        if isinstance(parent, Var):
            self._roots[child] = self.root(parent)

    def emit(self, rule: str, message: str, eqn=None, file=None, line=None,
             provenance=None) -> None:
        if eqn is not None and file is None:
            file, line = _loc(eqn)
            provenance = provenance or eqn.primitive.name
        self.findings.append(make_finding(rule, message, file=file,
                                          line=line, provenance=provenance))


def _as_open(j):
    return j.jaxpr if hasattr(j, "jaxpr") else j


def _consts_of(j) -> List[Any]:
    return list(getattr(j, "consts", ()) or ())


def _sub_jaxprs(eqn):
    """Yield (jaxpr-like, [(outer, inner_invar)...], [(inner_outvar,
    outer_outvar)...]) for every sub-jaxpr of ``eqn`` with its variable
    correspondence (best effort — unknown primitives fall back to a 1:1
    mapping when arities line up, else no mapping)."""
    name = eqn.primitive.name
    p = eqn.params
    out = []
    if name == "while":
        cj, bj = p["cond_jaxpr"], p["body_jaxpr"]
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        carry = list(eqn.invars[cn + bn:])
        c_open, b_open = _as_open(cj), _as_open(bj)
        out.append((cj, list(zip(list(eqn.invars[:cn]) + carry,
                                 c_open.invars)), []))
        out.append((bj, list(zip(list(eqn.invars[cn:cn + bn]) + carry,
                                 b_open.invars)),
                    list(zip(b_open.outvars, eqn.outvars))))
    elif name == "scan":
        j = p["jaxpr"]
        jo = _as_open(j)
        k = p.get("num_consts", 0) + p.get("num_carry", 0)
        out.append((j, list(zip(eqn.invars[:k], jo.invars[:k])),
                    list(zip(jo.outvars[:p.get("num_carry", 0)],
                             eqn.outvars[:p.get("num_carry", 0)]))))
    elif name == "cond":
        for br in p.get("branches", ()):
            bo = _as_open(br)
            out.append((br, list(zip(eqn.invars[1:], bo.invars)),
                        list(zip(bo.outvars, eqn.outvars))))
    else:
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            j = p.get(key)
            if j is None:
                continue
            jo = _as_open(j)
            inmap = list(zip(eqn.invars, jo.invars)) \
                if len(jo.invars) == len(eqn.invars) else []
            outmap = list(zip(jo.outvars, eqn.outvars)) \
                if len(jo.outvars) == len(eqn.outvars) else []
            out.append((j, inmap, outmap))
            break
    return out


# ---------------------------------------------------------------------------
# value chasing (A001 index provenance, A002 pattern match)
# ---------------------------------------------------------------------------

def _resolve(ctx: _Ctx, v, limit: int = 32):
    """Follow shape-preserving wrapper equations up the def chain."""
    for _ in range(limit):
        if not isinstance(v, Var):
            return v
        eqn = ctx.defs.get(v)
        if eqn is None or eqn.primitive.name not in _TRANSPARENT:
            return v
        src = next((iv for iv in eqn.invars if isinstance(iv, Var)),
                   eqn.invars[0] if eqn.invars else None)
        if src is None:
            return v
        v = src
    return v


def _is_const_operand(ctx: _Ctx, x) -> bool:
    return isinstance(x, Literal) or (isinstance(x, Var)
                                      and x in ctx.const_vals)


def _unique_base(ctx: _Ctx, v, depth: int = 0):
    """Resolve ``v`` through *injective* transformations to its base:
    shape-preserving wrappers, ``x ± const``, and ``select_n`` whose data
    branches share one base (jnp's negative-index normalization
    ``where(x < 0, x + n, x)``).  Injective steps preserve distinctness,
    so uniqueness of the base implies uniqueness of ``v``."""
    if depth > 16 or not isinstance(v, Var):
        return v
    eqn = ctx.defs.get(v)
    if eqn is None:
        return v
    name = eqn.primitive.name
    if name in _TRANSPARENT:
        src = next((iv for iv in eqn.invars if isinstance(iv, Var)), None)
        return v if src is None else _unique_base(ctx, src, depth + 1)
    if name in ("add", "sub"):
        data = [iv for iv in eqn.invars
                if not _is_const_operand(ctx, iv)]
        if len(data) == 1 and isinstance(data[0], Var):
            return _unique_base(ctx, data[0], depth + 1)
        return v
    if name == "select_n":
        bases = [_unique_base(ctx, b, depth + 1) for b in eqn.invars[1:]]
        if bases and all(b is bases[0] for b in bases[1:]):
            return bases[0]
        return v
    return v


def _indices_provably_unique(ctx: _Ctx, idx) -> bool:
    """True when the scatter's index operand is statically known
    collision-free: concrete non-negative unique indices, or an injective
    chain over an iota (e.g. ``.at[jnp.arange(n)]``)."""
    v = _unique_base(ctx, idx)
    arr = None
    if isinstance(v, Literal):
        arr = np.asarray(v.val)
    elif isinstance(v, Var) and v in ctx.const_vals:
        arr = np.asarray(ctx.const_vals[v])
    if arr is not None:
        flat = arr.reshape(-1)
        # negatives wrap through the normalization select, so a raw
        # uniqueness check only holds for the non-negative case
        return bool((flat >= 0).all()
                    and len(np.unique(flat)) == flat.size)
    if isinstance(v, Var):
        eqn = ctx.defs.get(v)
        if eqn is not None and eqn.primitive.name == "iota":
            return True
        # concatenation of iota-derived pieces etc. stays "dynamic"
    return False


def _n_updates(idx) -> int:
    aval = getattr(idx, "aval", None)
    shape = getattr(aval, "shape", ())
    return int(shape[0]) if shape else 1


# ---------------------------------------------------------------------------
# the walk
# ---------------------------------------------------------------------------

def _walk(ctx: _Ctx, jaxpr_like) -> None:
    jaxpr = _as_open(jaxpr_like)
    for cv, val in zip(jaxpr.constvars, _consts_of(jaxpr_like)):
        ctx.const_vals[cv] = val

    # per-jaxpr liveness for A004: last equation index using each var
    last_use: Dict[Var, int] = {}
    n_eqns = len(jaxpr.eqns)
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, Var):
                last_use[v] = i
    for v in jaxpr.outvars:
        if isinstance(v, Var):
            last_use[v] = n_eqns           # "used by the result"

    for i, eqn in enumerate(jaxpr.eqns):
        for ov in eqn.outvars:
            ctx.defs[ov] = eqn
        name = eqn.primitive.name

        if name == contracts.MARKER:
            src = eqn.invars[0]
            ctx.link(eqn.outvars[0], src)
            role = eqn.params.get("role")
            if role == "table":
                if isinstance(src, Var):
                    ctx.table_vars.add(src)
                ctx.table_vars.add(eqn.outvars[0])
            elif role and role.startswith("op_"):
                cs = ctx.site_map.get(eqn.params.get("site"))
                if cs is not None and isinstance(src, Var):
                    cs.vars[role[3:]] = src

        if name in SCATTER_PRIMS or name == "dynamic_update_slice":
            operand = eqn.invars[0]
            ctx.link(eqn.outvars[0], operand)
            is_table = isinstance(operand, Var) and (
                operand in ctx.table_vars
                or ctx.root(operand) in ctx.table_vars)
            if is_table:
                ctx.table_vars.add(eqn.outvars[0])
            if name in SCATTER_PRIMS:
                _rule_a001(ctx, eqn, is_table)

        if name == "while":
            _rule_a003(ctx, eqn)

        don = eqn.params.get("donated_invars") if name == "jit" else None
        if don and any(don):
            _rule_a004(ctx, eqn, don, last_use, i, n_eqns)

        for sub, inmap, outmap in _sub_jaxprs(eqn):
            for outer, inner in inmap:
                if isinstance(outer, Var) and isinstance(inner, Var):
                    if outer in ctx.table_vars \
                            or ctx.root(outer) in ctx.table_vars:
                        ctx.table_vars.add(inner)
                    ctx.link(inner, outer)
            _walk(ctx, sub)
            for inner, outer in outmap:
                if isinstance(inner, Var) and isinstance(outer, Var):
                    if inner in ctx.table_vars \
                            or ctx.root(inner) in ctx.table_vars:
                        ctx.table_vars.add(outer)
                    ctx.link(outer, inner)


# ---------------------------------------------------------------------------
# A001 — race detector
# ---------------------------------------------------------------------------

def _rule_a001(ctx: _Ctx, eqn, is_table: bool) -> None:
    if _sanctioned(eqn):
        return
    name = eqn.primitive.name
    operand, indices = eqn.invars[0], eqn.invars[1]
    if is_table:
        ctx.emit("A001",
                 "raw scatter write into AtomicTable data bypasses "
                 "atomics.execute — duplicate-index ordering is undefined "
                 "and the serialized-equivalence contract is lost; route "
                 "the update through repro.atomics.execute", eqn=eqn)
        return
    n = _n_updates(indices)
    if n <= 1:
        return                          # a single update cannot self-alias
    if eqn.params.get("unique_indices", False):
        return                          # caller vouched for distinctness
    if _indices_provably_unique(ctx, indices):
        return
    root = ctx.root(operand) if isinstance(operand, Var) else None
    writes = ctx.root_writes.setdefault(root, []) if root is not None else []
    writes.append(eqn)
    if name == "scatter":
        ctx.emit("A001",
                 f"set-style scatter with potentially-aliasing dynamic "
                 f"indices ({n} updates): XLA duplicate-index ordering is "
                 f"undefined — pass unique_indices=True if collisions are "
                 f"impossible, or use atomics.execute (Swp) for "
                 f"last-writer-wins semantics", eqn=eqn,
                 provenance="scatter")
    elif len(writes) > 1:
        ctx.emit("A001",
                 f"buffer receives multiple {name} writes with "
                 f"potentially-aliasing indices in one jaxpr — hand-rolled "
                 f"read-modify-write; use repro.atomics.execute for "
                 f"serialized-equivalent semantics", eqn=eqn,
                 provenance=name)


# ---------------------------------------------------------------------------
# A003 — unbounded-retry detector
# ---------------------------------------------------------------------------

def _contains_cas(ctx: _Ctx, jaxpr_like) -> bool:
    """True when the jaxpr (recursively) holds a CAS op-marker equation —
    i.e. some `atomics.execute(Cas(...))` was traced inside it."""
    jaxpr = _as_open(jaxpr_like)
    cached = ctx._cas_cache.get(id(jaxpr))
    if cached is not None:
        return cached
    found = False
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == contracts.MARKER \
                and eqn.params.get("kind") == "cas":
            found = True
            break
        if any(_contains_cas(ctx, sub) for sub, _, _ in _sub_jaxprs(eqn)):
            found = True
            break
    ctx._cas_cache[id(jaxpr)] = found
    return found


def _cond_influencing_positions(cond_open, nconsts: int) -> List[int]:
    """Carry positions whose value reaches the loop predicate (backward
    slice from the cond jaxpr's outputs)."""
    needed: Set[Var] = {v for v in cond_open.outvars if isinstance(v, Var)}
    for eqn in reversed(cond_open.eqns):
        if any(ov in needed for ov in eqn.outvars):
            needed.update(v for v in eqn.invars if isinstance(v, Var))
    carry = cond_open.invars[nconsts:]
    return [i for i, v in enumerate(carry) if v in needed]


def _is_counter_carry(ctx: _Ctx, body_open, nconsts: int, pos: int) -> bool:
    """True when carry ``pos`` is a monotone counter: its body output is
    ``add/sub(carry_in, constant)`` (through wrapper hops)."""
    if nconsts + pos >= len(body_open.invars) \
            or pos >= len(body_open.outvars):
        return False
    inv = body_open.invars[nconsts + pos]
    outv = body_open.outvars[pos]
    if not isinstance(outv, Var):
        return False
    defs = {ov: e for e in body_open.eqns for ov in e.outvars}
    v = outv
    for _ in range(8):                  # resolve convert/broadcast hops
        e = defs.get(v)
        if e is None:
            return False
        if e.primitive.name in _TRANSPARENT:
            v = next((iv for iv in e.invars if isinstance(iv, Var)), None)
            if v is None:
                return False
            continue
        break
    if e is None or e.primitive.name not in ("add", "sub"):
        return False
    ops = []
    for iv in e.invars:
        if isinstance(iv, Var):
            w = iv
            for _ in range(8):
                d = defs.get(w)
                if d is not None and d.primitive.name in _TRANSPARENT:
                    nxt = next((x for x in d.invars if isinstance(x, Var)),
                               None)
                    if nxt is None:
                        break
                    w = nxt
                else:
                    break
            ops.append(w)
        else:
            ops.append(iv)
    has_self = any(o is inv for o in ops)
    has_const = any(isinstance(o, Literal) or
                    (isinstance(o, Var) and o not in defs and o is not inv)
                    for o in ops)
    return has_self and has_const


def _rule_a003(ctx: _Ctx, eqn) -> None:
    if _sanctioned(eqn):
        return
    p = eqn.params
    body, cond = p["body_jaxpr"], p["cond_jaxpr"]
    if not _contains_cas(ctx, body):
        return                          # loop body issues no CAS
    cond_open, body_open = _as_open(cond), _as_open(body)
    cn, bn = p["cond_nconsts"], p["body_nconsts"]
    for pos in _cond_influencing_positions(cond_open, cn):
        if _is_counter_carry(ctx, body_open, bn, pos):
            return                      # a round counter bounds the loop
    ctx.emit("A003",
             "while_loop retries a CAS with a data-dependent predicate and "
             "no counter-like round bound — under contention this is the "
             "unbounded CAS storm of arxiv 1305.5800; use "
             "atomics.execute_until(make_ops, max_rounds=..., policy=...) "
             "or add a bounded round counter to the carry", eqn=eqn)


# ---------------------------------------------------------------------------
# A004 — donation safety (jaxpr half)
# ---------------------------------------------------------------------------

def _rule_a004(ctx: _Ctx, eqn, donated, last_use, idx: int,
               n_eqns: int) -> None:
    if _sanctioned(eqn):
        return
    for i, d in enumerate(donated):
        if not d or i >= len(eqn.invars):
            continue
        v = eqn.invars[i]
        if not isinstance(v, Var):
            continue
        lu = last_use.get(v, -1)
        if lu > idx:
            how = "the function result" if lu == n_eqns \
                else "a later equation"
            ctx.emit("A004",
                     f"buffer donated to a jitted call (donate_argnums) is "
                     f"still read by {how} — after donation the buffer may "
                     f"alias the callee's output; keep a copy or drop the "
                     f"donation", eqn=eqn, provenance="jit donated_invars")


# ---------------------------------------------------------------------------
# A002 / A005 — call-site rules (run even when the trace aborted)
# ---------------------------------------------------------------------------

def _rule_a002(ctx: _Ctx, cs: CallSite) -> None:
    cas_cn = OP_KINDS["cas"].CONSENSUS_NUMBER
    faa_cn = OP_KINDS["faa"].CONSENSUS_NUMBER

    def _say(alt: str, why: str) -> None:
        ctx.findings.append(make_finding(
            "A002",
            f"Cas batch (consensus number {cas_cn}) {why} — express it as "
            f"atomics.{alt} (consensus number {faa_cn}): same cost on every "
            f"tier (the paper's headline result), combinable instead of "
            f"serialized, and no retry loop needed (arxiv 1802.03844)",
            file=cs.file, line=cs.line, provenance="atomics.Cas"))

    c_vals = cs.concrete.get("values")
    c_exp = cs.concrete.get("expected")
    v_vals = cs.vars.get("values")
    v_exp = cs.vars.get("expected")

    if c_vals is not None and c_exp is not None:
        try:
            if np.array_equal(np.broadcast_to(c_exp, c_vals.shape), c_vals):
                _say("execute(..., need_fetched=True) read or Swp",
                     "writes back exactly its expected value (a no-op when "
                     "it succeeds)")
                return
            diff = c_vals - np.broadcast_to(c_exp, c_vals.shape)
            if len(np.unique(diff)) == 1:
                _say("Faa", f"always adds a constant {diff.reshape(-1)[0]} "
                            f"to its expected value")
                return
        except Exception:  # noqa: BLE001 — dtype mismatch etc.
            return
    if v_vals is None:
        return
    rv = _resolve(ctx, v_vals)
    re_ = _resolve(ctx, v_exp) if v_exp is not None else None
    if re_ is not None and rv is re_:
        _say("execute(..., need_fetched=True) read or Swp",
             "writes back exactly its expected value (a no-op when it "
             "succeeds)")
        return
    eqn = ctx.defs.get(rv) if isinstance(rv, Var) else None
    if eqn is None:
        return
    name = eqn.primitive.name
    if name not in ("add", "sub", "max", "min"):
        return
    operands = [_resolve(ctx, iv) for iv in eqn.invars]
    matches_exp = any(o is re_ for o in operands if re_ is not None)
    if not matches_exp:
        return
    if name in ("add", "sub"):
        _say("Faa", "computes value = expected ± delta (the classic "
                    "fetch-and-add retry shape)")
    elif name == "max":
        _say("Max", "computes value = max(expected, x)")
    else:
        _say("Min", "computes value = min(expected, x)")


def _rule_a005(ctx: _Ctx, callsites: List[CallSite]) -> None:
    for cs in callsites:
        if cs.site == "execute" and cs.table_sharded \
                and cs.axes_bound is False:
            ctx.findings.append(make_finding(
                "A005",
                f"execute on a table sharded over mesh axes "
                f"{cs.axis_names!r} with those axes unbound — the call is "
                f"outside shard_map (or the shard_map does not carry the "
                f"table's declared axis/replica_axes); wrap it in "
                f"repro.sharding.shard_map_compat over exactly those axes",
                file=cs.file, line=cs.line, provenance="atomics.execute"))
    # mixed reverse_ranks across one combine tree: group sharded execute
    # sites by the axes they bind
    by_axes: Dict[Tuple[str, ...], List[CallSite]] = {}
    for cs in callsites:
        if cs.site == "execute" and cs.table_sharded and cs.axes_bound:
            by_axes.setdefault(cs.axis_names, []).append(cs)
    for axes, group in by_axes.items():
        fwd = [c for c in group if not c.reverse_ranks]
        rev = [c for c in group if c.reverse_ranks]
        if rev and fwd and not any(c.need_fetched for c in fwd):
            c = rev[0]
            ctx.findings.append(make_finding(
                "A005",
                f"mixed reverse_ranks directions over axes {axes!r} but no "
                f"forward pass fetches pre-images (need_fetched=False "
                f"everywhere): a reversed second pass is only coherent as "
                f"a revert of fetched values (the SWP+revert scheme) — "
                f"fetch on the forward pass or drop reverse_ranks",
                file=c.file, line=c.line, provenance="atomics.execute"))


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def run(tr: TraceResult) -> List[Finding]:
    """Apply every rule to a trace; returns findings (unsorted, raw)."""
    ctx = _Ctx(tr)
    if tr.closed is not None:
        _walk(ctx, tr.closed)
    _rule_a005(ctx, tr.callsites)
    for cs in tr.callsites:
        if cs.site == "execute" and cs.kind == "cas":
            _rule_a002(ctx, cs)
    if tr.error is not None:
        # an aborted trace with no diagnosed cause is itself a finding —
        # the analyzer must not silently report "clean" on it
        diagnosed = any(f.rule == "A005" for f in ctx.findings)
        if not diagnosed:
            ctx.findings.append(make_finding(
                "A000", f"trace aborted: {type(tr.error).__name__}: "
                        f"{tr.error}", provenance="jax.make_jaxpr"))
    for msg in tr.observer_errors:
        ctx.findings.append(make_finding(
            "A000", f"contract observer error (analysis bug, not a code "
                    f"finding): {msg.splitlines()[-1]}",
            provenance="contracts.observe"))
    return ctx.findings
