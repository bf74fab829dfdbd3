"""The program's named scopes and host spans in a profiler trace.

An addition to ``trace.py``, whose reduction it leaves as it is:
``reduce_scoped`` returns ``trace.reduce_events``'s keys, computed the
same way, plus

* ``scope_s``: for each scope path (the top level, ``select``, and two
  levels, ``select/catalog``), the union of the intervals of its device
  operations on each device, averaged over the devices as ``busy_s``
  is. A union, not a sum, so a ``while`` whose body operations are
  events of their own counts once;
* ``unscoped_s``: the same for the operations under no scope (the step
  key's random bits, copies and layout changes the compiler adds);
* ``span_s`` / ``span_n``: host seconds and count of each host span,
  the benchmark's ``bench.*`` and the program's ``repro.*``;
* ``idle_by_span``: the device's idle seconds by the innermost host
  span of either family that covers each gap's midpoint.

An operation's scope is the path of ``jax.named_scope`` names in its
HLO ``op_name`` (``jit(train_step)/transpose(jvp(backbone))/while/
body/dot_general`` → ``backbone/while/body``): ``jit(...)`` components
dropped, ``jvp(...)`` and ``transpose(...)`` unwrapped (an empty one is
a ``shard_map`` level), the last component, the primitive, left out,
and the path cut at the first component that is not a plain name (a
function's qualified name, an einsum's subscripts): JAX names the step
key's random bits after the function that splits it. The v5e's
``XLA Ops`` events name the HLO instruction and carry no ``op_name``
(they hold only device offset and duration), so ``read_scoped`` takes
it from the compiled program's text (``op_names_from_hlo``).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

from harness import trace

SPAN_PREFIXES = ("bench.", "repro.")
MODULE_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Op(trace.Event):
    """A device operation with its scope path ("" for none)."""

    scope: str = ""


_WRAPPED = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
_NAME = re.compile(r"^[A-Za-z_]\w*$")


def scope_of(op_name: Optional[str]) -> str:
    """The scope path of an HLO ``op_name`` ("" for none)."""
    if not op_name:
        return ""
    parts = []
    for c in op_name.split("/")[:-1]:
        if c.startswith("jit("):
            continue
        m = _WRAPPED.match(c)
        while m:
            c = m.group(1)
            m = _WRAPPED.match(c)
        if not c:
            continue
        if not _NAME.match(c):
            break
        parts.append(c)
    return "/".join(parts)


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                    r"metadata=\{[^}]*op_name=\"([^\"]*)\"")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)


def op_names_from_hlo(text: str) -> Tuple[str, Dict[str, str]]:
    """The module name of a compiled program's ``as_text()`` and the
    ``op_name`` of each of its instructions that has one."""
    m = _MODULE.search(text)
    names = {}
    for line in text.splitlines():
        i = _INSTR.match(line)
        if i:
            names[i.group(1)] = i.group(2)
    return (m.group(1) if m else ""), names


def read_scoped(path: str, module: str = "",
                op_names: Optional[Mapping[str, str]] = None
                ) -> List[trace.Event]:
    """Device operations of every TPU plane, and the ``bench.*`` and
    ``repro.*`` host spans, from one ``.xplane.pb``. The TPU's ``XLA
    Ops`` events carry no ``op_name``; an operation takes its scope from
    ``op_names`` (instruction → ``op_name`` of one program,
    ``op_names_from_hlo``) where the ``XLA Modules`` event around it is
    that program's (its name starts with ``module``). Raises as
    ``trace.read_xplane`` does."""
    from jax.profiler import ProfileData

    op_names = op_names or {}
    events: List[trace.Event] = []
    n_devices = 0
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            if trace.OP_LINE not in lines:
                raise ValueError(f"{plane.name} has no {trace.OP_LINE!r} "
                                 f"line: {sorted(lines)}")
            n_devices += 1
            ours = sorted(
                (float(m.start_ns), float(m.start_ns + m.duration_ns))
                for m in (lines[MODULE_LINE].events
                          if MODULE_LINE in lines else ())
                if m.name.startswith(module))
            starts = [a for a, _ in ours]
            for e in lines[trace.OP_LINE].events:
                t = float(e.start_ns)
                i = bisect.bisect_right(starts, t) - 1
                name = (op_names.get(trace.instruction(e.name))
                        if i >= 0 and t <= ours[i][1] else None)
                events.append(Op("op", plane.name, e.name, t,
                                 float(e.duration_ns), scope_of(name)))
        elif plane.name == trace.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        events.append(trace.Event(
                            "span", "", e.name, float(e.start_ns),
                            float(e.duration_ns)))
    if not n_devices:
        raise ValueError(f"no {trace.DEVICE_PREFIX}* plane in {path}")
    return events


def _levels(scope: str) -> Iterable[str]:
    parts = scope.split("/")
    yield parts[0]
    if len(parts) > 1:
        yield "/".join(parts[:2])


def reduce_scoped(events: Sequence[trace.Event], window_s: float,
                  kernels: Sequence[str] = ()) -> Dict:
    """``trace.reduce_events`` of ``events``, with ``scope_s``,
    ``unscoped_s``, ``span_s``, ``span_n`` and ``idle_by_span``."""
    out = trace.reduce_events(events, window_s, kernels)
    ops = [e for e in events if e.kind == "op"]
    spans = sorted((e for e in events if e.kind == "span"),
                   key=lambda e: e.start_ns)
    span_s: Dict[str, float] = collections.defaultdict(float)
    span_n: Dict[str, int] = collections.defaultdict(int)
    for s in spans:
        span_s[s.name] += s.dur_ns * 1e-9
        span_n[s.name] += 1

    by_key: Dict[Tuple[str, str], List[Tuple[float, float]]] = (
        collections.defaultdict(list))
    by_device: Dict[str, List[Tuple[float, float]]] = (
        collections.defaultdict(list))
    for e in ops:
        iv = (e.start_ns, e.end_ns)
        by_device[e.device].append(iv)
        scope = getattr(e, "scope", "")
        for key in (_levels(scope) if scope else ("",)):
            by_key[(key, e.device)].append(iv)
    n_dev = max(1, len(by_device))
    scope_s: Dict[str, float] = collections.defaultdict(float)
    for (key, _), ivs in by_key.items():
        scope_s[key] += sum(b - a for a, b in trace._union(ivs)) * 1e-9 / n_dev

    starts = [s.start_ns for s in spans]
    longest = max((s.dur_ns for s in spans), default=0.0)
    outer = [s for s in spans if s.dur_ns >= 0.1 * longest]
    idle: Dict[str, float] = collections.defaultdict(float)
    for ivs in by_device.values():
        merged = trace._union(ivs)
        for (_, end), (nxt, _) in zip(merged, merged[1:]):
            label = trace._span_at(spans, starts, outer, (end + nxt) / 2)
            idle[label] += (nxt - end) * 1e-9 / n_dev

    out["unscoped_s"] = scope_s.pop("", 0.0)
    out["scope_s"] = dict(sorted(scope_s.items()))
    out["span_s"] = dict(span_s)
    out["span_n"] = dict(span_n)
    out["idle_by_span"] = dict(sorted(idle.items(), key=lambda kv: -kv[1]))
    return out


def ms_per_step(r, table: str, key: str) -> Optional[float]:
    """``trace[table][key]`` in ms per step of the window; ``None`` where
    the trace has no such entry (a program without the scope or span)."""
    t, steps = r["trace"], r["counters"].get("steps")
    value = (t or {}).get(table, {}).get(key)
    if not value or not steps:
        return None
    return 1e3 * value / steps


def mean_span_ms(r, name: str) -> Optional[float]:
    """Mean length (ms) of the host span ``name`` in the window."""
    t = r["trace"] or {}
    n = t.get("span_n", {}).get(name)
    return 1e3 * t["span_s"][name] / n if n else None
