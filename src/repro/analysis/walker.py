"""Jaxpr walker: flatten a traced serving graph into scope-tagged,
provenance-annotated equation records.

The walker recurses through call primitives (``jit``, ``scan``,
``while``, ``cond``, ``custom_*``, ``remat``) and produces one
:class:`EqnRecord` per equation at every nesting depth, carrying

  * the **absolute name-scope stack** — subjaxpr equations store their
    ``source_info.name_stack`` *relative* to their jaxpr, so the walker
    prefixes the enclosing equation's stack while descending; rules
    match on ``jax.named_scope`` tags the serving stack plants
    (``pum_linear<N>``, ``qact``, ``kv_pool_write``, ...);
  * **provenance**: for every operand, the set of *top-level invar
    indices* it (transitively) depends on.  Scan and while carries are
    iterated to a fixpoint, so a value flowing through the layer-group
    scan still maps back to the KV pool / block table / active-mask
    invar it came from.  This is what lets the masked-scatter rule ask
    "do this scatter's *indices* depend on the active mask?" statically.

The walker deliberately avoids importing jax internals: vars, literals
and (closed) jaxprs are duck-typed.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from collections.abc import Sequence
from typing import Any

EMPTY: Frozenset[int] = frozenset()

# Call primitives whose subjaxpr invars map 1:1 onto the equation's
# invars (no carry/const split).
_ONE_TO_ONE_CALLS = {
    "jit", "closed_call", "remat2", "custom_jvp_call", "custom_vjp_call",
}


def _is_literal(v: Any) -> bool:
    return hasattr(v, "val")


def _as_jaxpr(obj: Any):
    """ClosedJaxpr | Jaxpr -> the open Jaxpr (or None)."""
    if obj is None:
        return None
    if hasattr(obj, "eqns") and hasattr(obj, "invars"):
        return obj                                   # open Jaxpr
    inner = getattr(obj, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner                                 # ClosedJaxpr
    return None


def _stack_components(eqn: Any) -> tuple[str, ...]:
    ns = getattr(eqn.source_info, "name_stack", None)
    if ns is None:
        return ()
    s = str(ns)
    return tuple(c for c in s.split("/") if c)


def _union(sets: Sequence[Frozenset[int]]) -> Frozenset[int]:
    out: Frozenset[int] = EMPTY
    for s in sets:
        out = out | s
    return out


@dataclass
class EqnRecord:
    """One equation, anywhere in the nested jaxpr."""
    eqn: Any
    prim: str
    stack: tuple[str, ...]             # absolute named_scope components
    in_deps: tuple[Frozenset[int], ...]  # per-operand top-level invar deps
    out_deps: Frozenset[int]
    depth: int                         # subjaxpr nesting depth

    def in_scope(self, pattern: str) -> bool:
        rx = re.compile(pattern)
        return any(rx.fullmatch(c) for c in self.stack)

    @property
    def out_avals(self) -> list[Any]:
        return [getattr(v, "aval", None) for v in self.eqn.outvars]


@dataclass
class GraphIndex:
    """The walked graph: flat records + invar labelling."""
    records: list[EqnRecord]
    invar_labels: list[str] = field(default_factory=list)

    def invars_matching(self, pattern: str) -> Frozenset[int]:
        """Top-level invar indices whose label matches ``pattern``
        (regex, searched anywhere in the label)."""
        rx = re.compile(pattern)
        return frozenset(i for i, lab in enumerate(self.invar_labels)
                         if rx.search(lab))

    def by_prim(self, name: str) -> list[EqnRecord]:
        return [r for r in self.records if r.prim == name]

    def in_scope(self, pattern: str) -> list[EqnRecord]:
        """Records whose stack contains a component fullmatching
        ``pattern``."""
        rx = re.compile(pattern)
        return [r for r in self.records
                if any(rx.fullmatch(c) for c in r.stack)]

    def scope_instances(self, pattern: str) -> dict[str, list[EqnRecord]]:
        """Group records by *scope instance*: the stack prefix up to and
        including the first component fullmatching ``pattern``.  With
        trace-unique scope names (``pum_linear<N>``) every MVM call site
        becomes its own instance."""
        rx = re.compile(pattern)
        out: dict[str, list[EqnRecord]] = {}
        for r in self.records:
            for i, c in enumerate(r.stack):
                if rx.fullmatch(c):
                    out.setdefault("/".join(r.stack[:i + 1]), []).append(r)
                    break
        return out


# ---------------------------------------------------------------------------
# The walk
# ---------------------------------------------------------------------------

def _read(env: dict[Any, Frozenset[int]], v: Any) -> Frozenset[int]:
    if _is_literal(v):
        return EMPTY
    return env.get(v, EMPTY)


def _run_inner(sub: Any, seeds: Sequence[Frozenset[int]],
               prefix: tuple[str, ...], depth: int,
               records: list[EqnRecord] | None,
               ) -> list[Frozenset[int]]:
    jaxpr = _as_jaxpr(sub)
    env: dict[Any, Frozenset[int]] = {}
    invars = list(jaxpr.invars)
    assert len(invars) == len(seeds), (len(invars), len(seeds))
    for v, s in zip(invars, seeds):
        env[v] = s
    for cv in getattr(jaxpr, "constvars", ()):
        env[cv] = EMPTY
    return _process(jaxpr, env, prefix, depth, records)


def _call_outputs(eqn: Any, in_deps: tuple[Frozenset[int], ...],
                  stack: tuple[str, ...], depth: int,
                  records: list[EqnRecord] | None,
                  ) -> list[Frozenset[int]] | None:
    """Primitive-specific subjaxpr handling.  Returns per-outvar deps,
    or None for primitives without (walkable) subjaxprs."""
    prim = eqn.primitive.name
    params = eqn.params

    if prim in _ONE_TO_ONE_CALLS:
        sub = params.get("jaxpr") or params.get("call_jaxpr")
        if _as_jaxpr(sub) is None:
            return None
        return _run_inner(sub, list(in_deps), stack, depth + 1, records)

    if prim == "scan":
        sub = params["jaxpr"]
        nc, ncar = params["num_consts"], params["num_carry"]
        consts = list(in_deps[:nc])
        carry = list(in_deps[nc:nc + ncar])
        xs = list(in_deps[nc + ncar:])
        for _ in range(len(carry) * 32 + 2):   # fixpoint (monotone, bounded)
            outs = _run_inner(sub, consts + carry + xs, stack,
                              depth + 1, None)
            new_carry = [c | o for c, o in zip(carry, outs[:ncar])]
            if new_carry == carry:
                break
            carry = new_carry
        outs = _run_inner(sub, consts + carry + xs, stack,
                          depth + 1, records)
        return carry + outs[ncar:]

    if prim == "while":
        cond_sub, body_sub = params["cond_jaxpr"], params["body_jaxpr"]
        cn, bn = params["cond_nconsts"], params["body_nconsts"]
        cond_consts = list(in_deps[:cn])
        body_consts = list(in_deps[cn:cn + bn])
        carry = list(in_deps[cn + bn:])
        for _ in range(len(carry) * 32 + 2):
            outs = _run_inner(body_sub, body_consts + carry, stack,
                              depth + 1, None)
            new_carry = [c | o for c, o in zip(carry, outs)]
            if new_carry == carry:
                break
            carry = new_carry
        _run_inner(body_sub, body_consts + carry, stack, depth + 1, records)
        _run_inner(cond_sub, cond_consts + carry, stack, depth + 1, records)
        return carry

    if prim == "cond":
        branches = params["branches"]
        pred = in_deps[0]
        ops = list(in_deps[1:])
        per_branch = [_run_inner(br, ops, stack, depth + 1, records)
                      for br in branches]
        n_out = len(per_branch[0])
        return [pred | _union([b[i] for b in per_branch])
                for i in range(n_out)]

    return None


def _process(jaxpr: Any, env: dict[Any, Frozenset[int]],
             prefix: tuple[str, ...], depth: int,
             records: list[EqnRecord] | None,
             ) -> list[Frozenset[int]]:
    for eqn in jaxpr.eqns:
        in_deps = tuple(_read(env, v) for v in eqn.invars)
        stack = prefix + _stack_components(eqn)
        out_list = _call_outputs(eqn, in_deps, stack, depth, records)
        if out_list is None:
            # leaf primitive (or opaque call, e.g. pallas_call):
            # conservative flat propagation
            flat = _union(in_deps)
            out_list = [flat] * len(eqn.outvars)
        if records is not None:
            records.append(EqnRecord(eqn, eqn.primitive.name, stack,
                                     in_deps, _union(out_list), depth))
        for ov, od in zip(eqn.outvars, out_list):
            env[ov] = od
    return [_read(env, v) for v in jaxpr.outvars]


def index_graph(closed: Any,
                invar_labels: Sequence[str] | None = None) -> GraphIndex:
    """Walk a ClosedJaxpr into a :class:`GraphIndex`.

    ``invar_labels`` names the top-level invars (one label per flattened
    argument leaf, e.g. ``states[0]['k_pool']``); rules use them to
    identify the KV pool / block table / active-mask inputs.
    """
    jaxpr = _as_jaxpr(closed)
    env: dict[Any, Frozenset[int]] = {}
    for i, v in enumerate(jaxpr.invars):
        env[v] = frozenset((i,))
    for cv in getattr(jaxpr, "constvars", ()):
        env[cv] = EMPTY
    records: list[EqnRecord] = []
    _process(jaxpr, env, (), 0, records)
    labels = list(invar_labels) if invar_labels is not None else [
        f"invar{i}" for i in range(len(jaxpr.invars))]
    assert len(labels) == len(jaxpr.invars), (
        f"invar label count {len(labels)} != invar count "
        f"{len(jaxpr.invars)}")
    return GraphIndex(records, labels)
