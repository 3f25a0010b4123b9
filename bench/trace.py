"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

The traced window is the harness's ``bench.trace`` annotation on the
host.  Within it, for each TPU device plane:

* busy time: the union of the intervals of the ``XLA Ops`` events;
* device time per XLA module (``XLA Modules`` line), by module name;
* self time per operation: an op's duration less the ops nested in it
  (a ``while`` holds its body's ops), so the totals add up to busy time.
  An op is keyed by the module event that holds it on the same plane and
  its HLO name, since HLO names are unique only within one module.

Busy time and module times are averaged over the chips; op times are
summed over them.  Each idle gap of the first chip is put down to the
innermost harness span (``bench.``, ``cox.``, ``serve.``) that was open
on the host at the gap's midpoint, or to ``(no span)``.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW_SPAN = "bench.trace"
SPAN_PREFIXES = ("bench.", "cox.", "serve.")
NO_SPAN = "(no span)"
NO_MODULE = "(no module)"

# an op: (the module event that holds it, its HLO name)
OpKey = Tuple[str, str]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    chips: int
    module_s: Dict[str, float]
    op_self_s: Dict[OpKey, float]
    op_text: Dict[OpKey, str]
    idle_gaps: List[Tuple[str, float]]

    def ops_matching(self, pattern: str) -> float:
        """Self seconds of every op whose HLO text matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(
            s for k, s in self.op_self_s.items() if rx.search(self.op_text[k])
        )

    def idle_percent(self):
        """Share of the window with no operation on the device, mean over
        chips; None for an empty window."""
        if self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def top_ops(self, n: int = 10) -> List[Tuple[OpKey, float]]:
        return sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:n]

    def op_label(self, key: OpKey) -> str:
        """``jit_step(30632...): %fusion.12 = bf16[...] fusion(...)``."""
        return f"{key[0]}: {self.op_text[key]}"


def find_xplane(log_dir: str) -> str:
    pattern = os.path.join(log_dir, "**", "*.xplane.pb")
    found = sorted(glob.glob(pattern, recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def module_name(name: str) -> str:
    """``jit_step(3063205380766041769)`` -> ``jit_step``."""
    return name.split("(", 1)[0]


def _union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: int, b: int, lo: int, hi: int) -> Tuple[int, int]:
    return max(a, lo), min(b, hi)


def _self_times(events: Sequence[Tuple[int, int, OpKey]]) -> Dict[OpKey, int]:
    """Self time per op of properly nested events (start, end, key)."""
    out: Dict[OpKey, int] = {}
    stack: List[List] = []  # [end, name, start, nested time]

    def close(top):
        end, name, start, child = top
        out[name] = out.get(name, 0) + (end - start) - child
        if stack:
            stack[-1][3] += end - start

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        stack.append([end, name, start, 0])
    while stack:
        close(stack.pop())
    return out


def _events(line) -> List[Tuple[int, int, str]]:
    return [
        (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name) for e in line.events
    ]


def _holder(modules: Sequence[Tuple[int, int, str]]):
    """The module event whose interval holds a time, or ``NO_MODULE``;
    module events on one plane do not overlap."""
    modules = sorted(modules)
    starts = [a for a, _, _ in modules]

    def find(t: int) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return modules[i][2] if i >= 0 and t < modules[i][1] else NO_MODULE

    return find


def reduce(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_planes(list(ProfileData.from_file(path).planes))


def reduce_planes(planes) -> TraceSummary:
    """The reduction of the planes of a trace: objects with ``name`` and
    ``lines``, each line with ``name`` and ``events``, each event with
    ``name``, ``start_ns`` and ``duration_ns``."""
    spans: List[Tuple[int, int, str]] = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line) if e[2].startswith(SPAN_PREFIXES)]
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span on the host")
    lo, hi = windows[-1][0], windows[-1][1]

    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("trace has no TPU device plane")
    busy_total = 0
    module_ns: Dict[str, int] = {}
    op_ns: Dict[OpKey, int] = {}
    op_text: Dict[OpKey, str] = {}
    first_busy: List[Tuple[int, int]] = []
    for i, plane in enumerate(sorted(devices, key=lambda p: p.name)):
        lines = {line.name: line for line in plane.lines}
        modules = _events(lines["XLA Modules"]) if "XLA Modules" in lines else []
        for a, b, text in modules:
            a, b = _clip(a, b, lo, hi)
            if b > a:
                name = module_name(text)
                module_ns[name] = module_ns.get(name, 0) + (b - a)
        holder = _holder(modules)
        ops = []
        if "XLA Ops" in lines:
            for a, b, text in _events(lines["XLA Ops"]):
                a, b = _clip(a, b, lo, hi)
                if b > a:
                    key = (holder(a), op_name(text))
                    op_text.setdefault(key, text)
                    ops.append((a, b, key))
        busy = _union([(a, b) for a, b, _ in ops])
        busy_total += sum(b - a for a, b in busy)
        if i == 0:
            first_busy = busy
        for key, ns in _self_times(ops).items():
            op_ns[key] = op_ns.get(key, 0) + ns

    chips = len(devices)
    gaps = _idle_gaps(first_busy, lo, hi, [s for s in spans if s[2] != WINDOW_SPAN])
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total * 1e-9 / chips,
        chips=chips,
        module_s={k: v * 1e-9 / chips for k, v in module_ns.items()},
        op_self_s={k: v * 1e-9 for k, v in op_ns.items()},
        op_text=op_text,
        idle_gaps=gaps,
    )


def _idle_gaps(busy, lo, hi, spans) -> List[Tuple[str, float]]:
    """Idle time within [lo, hi) summed by the innermost host span open
    at each gap's midpoint, largest first."""
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    by_span: Dict[str, int] = {}
    pending, j, active = sorted(spans), 0, []
    for a, b in gaps:
        mid = (a + b) // 2
        while j < len(pending) and pending[j][0] <= mid:
            active.append(pending[j])
            j += 1
        active = [s for s in active if s[1] > mid]
        # harness spans nest, so the open span that started last is innermost
        name = max(active)[2] if active else NO_SPAN
        by_span[name] = by_span.get(name, 0) + (b - a)
    return sorted(((k, v * 1e-9) for k, v in by_span.items()), key=lambda kv: -kv[1])
