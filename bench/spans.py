"""Arithmetic over the program's own ``repro.obs`` spans, as the harness
drains them from the traced window (``Context.spans``).

A span's descendants are found by ``parent_id`` within its ``trace_id``,
so spans of two traces never mix.  "Under" a span means the outermost
descendants of a name: a span of that name nested in another of the same
name below the root is not counted twice.  Times are in milliseconds; a
function returns None when the window holds none of the spans it needs.
"""
from __future__ import annotations

from collections import defaultdict


def children(spans) -> dict:
    """Each span's children, keyed by (trace id, parent span id)."""
    kids = defaultdict(list)
    for s in spans:
        kids[(s.trace_id, s.parent_id)].append(s)
    return kids


def under(kids: dict, root, name: str) -> list:
    """The outermost descendants of ``root`` named ``name``."""
    found, stack = [], list(kids.get((root.trace_id, root.span_id), ()))
    while stack:
        s = stack.pop()
        if s.name == name:
            found.append(s)
        else:
            stack.extend(kids.get((s.trace_id, s.span_id), ()))
    return found


def _per_root(spans, root: str, name: str):
    """The ``root`` spans and, for each, the summed seconds of ``name``
    under it; None when there is no root, or no ``name`` under any."""
    roots = [s for s in spans if s.name == root]
    kids = children(spans)
    inner = [under(kids, r, name) for r in roots]
    if not any(inner):
        return None
    return roots, [sum(s.duration for s in found) for found in inner]


def summed_per_root_ms(spans, root: str, name: str) -> float | None:
    """Summed duration of ``name`` under ``root`` spans, over the number
    of ``root`` spans."""
    got = _per_root(spans, root, name)
    if got is None:
        return None
    roots, seconds = got
    return 1e3 * sum(seconds) / len(roots)


def mean_less_ms(spans, root: str, name: str) -> float | None:
    """Mean over ``root`` spans of the duration less ``name`` under it."""
    got = _per_root(spans, root, name)
    if got is None:
        return None
    roots, seconds = got
    return 1e3 * sum(r.duration - s for r, s in zip(roots, seconds)) / len(roots)


def mean_ms(spans, name: str) -> float | None:
    """Mean duration of the spans named ``name``."""
    durations = [s.duration for s in spans if s.name == name]
    if not durations:
        return None
    return 1e3 * sum(durations) / len(durations)
