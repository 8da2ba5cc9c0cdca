"""Jaxpr walking core: one recursive equation iterator for every audit.

Every rule in :mod:`repro.analysis.rules` — and the call sites that used to
carry private walkers (kernel_bench's aval scan, the executor tests' eqn
counter) — sees the traced program through this module, so "recurse into
scan/cond/switch/custom_vjp/shard_map/pallas_call sub-jaxprs" is defined in
exactly one place.

The recursion contract: an equation parameter contributes a sub-jaxpr when
it is a ``ClosedJaxpr`` (has ``.jaxpr``), a raw ``Jaxpr`` (has ``.eqns`` —
shard_map bodies), or a list/tuple of either (``cond``'s ``branches``).
That matches how jax stores the bodies of ``scan``/``while``/``cond``
/``pjit``/``custom_vjp_call_jaxpr``/``shard_map``/``remat`` and the Pallas
kernel body in ``pallas_call``'s ``jaxpr`` param.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Iterator, Tuple


@dataclasses.dataclass(frozen=True)
class EqnSite:
    """One equation plus where it sits in the sub-jaxpr tree.

    ``path`` is a tuple of ``"<primitive>.<param>"`` segments (with an
    ``[i]`` suffix when the param holds several sub-jaxprs, e.g.
    ``cond.branches[1]``) from the root to the equation's enclosing body.
    """
    eqn: Any
    path: Tuple[str, ...] = ()

    @property
    def prim(self) -> str:
        return self.eqn.primitive.name

    def where(self) -> str:
        return "/".join(self.path + (self.prim,))

    def in_cond_branch(self) -> bool:
        """True when the equation executes only on some branches of an
        enclosing ``cond``/``switch`` (the static-deadlock danger zone)."""
        return any(seg.startswith("cond.branches") for seg in self.path)


def as_jaxpr(jaxpr_like):
    """Accept a ClosedJaxpr or a raw Jaxpr; return the raw Jaxpr."""
    return jaxpr_like.jaxpr if hasattr(jaxpr_like, "jaxpr") else jaxpr_like


def subjaxprs(param) -> Iterator[Any]:
    """The raw sub-jaxprs held by one equation parameter (see module doc)."""
    if hasattr(param, "jaxpr"):            # ClosedJaxpr
        yield param.jaxpr
    elif hasattr(param, "eqns"):           # raw Jaxpr (shard_map body, ...)
        yield param
    elif isinstance(param, (list, tuple)):
        for p in param:
            yield from subjaxprs(p)


def iter_eqns(jaxpr_like, path: Tuple[str, ...] = ()) -> Iterator[EqnSite]:
    """Depth-first over every equation, recursing into sub-jaxprs."""
    for eqn in as_jaxpr(jaxpr_like).eqns:
        yield EqnSite(eqn, path)
        for key, param in eqn.params.items():
            subs = list(subjaxprs(param))
            for i, sub in enumerate(subs):
                seg = f"{eqn.primitive.name}.{key}"
                if len(subs) > 1:
                    seg += f"[{i}]"
                yield from iter_eqns(sub, path + (seg,))


def count_eqns(jaxpr_like) -> int:
    """Total equation count including sub-jaxpr bodies (unrolled tick
    copies, kernel bodies, and cond branches are all visible)."""
    return sum(1 for _ in iter_eqns(jaxpr_like))


def iter_eqn_avals(jaxpr_like) -> Iterator[Tuple[EqnSite, Any]]:
    """(site, aval) for every equation OUTPUT in the whole tree — the
    intermediate-buffer view the shape lints audit."""
    for site in iter_eqns(jaxpr_like):
        for var in site.eqn.outvars:
            yield site, var.aval
