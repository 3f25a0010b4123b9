"""The program's own spans, and the device's XLA modules, in a traced run.

The program writes ``jax.profiler.TraceAnnotation`` spans where its work
happens (``cox.*`` in ``core/api.py``, ``core/streams.py`` and
``core/graphs.py``; ``serve.*`` in ``launch/serve.py``) and jits each COX
program under a stable name (XLA module ``jit_cox_<kernel>``, a captured
graph ``jit_cox_graph_<name>``).  This module reads the ``--trace 1``
run's ``.xplane.pb`` once per process for the readers in
``bench/metrics/`` that take them.  It keeps the host events named in
:data:`SPANS` that lie inside the ``bench.trace`` window, and each TPU
device plane's ``XLA Modules`` events, clipped to the window.  It never
walks ``XLA Ops``: ``bench/trace.py`` has walked them once already.

A program without these spans or names (an older commit) gives empty
readings, and the readers then give nothing.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

from bench.trace import DEVICE_PLANE, WINDOW_SPAN, find_xplane, module_name

SPANS = frozenset(
    {
        "cox.resolve",
        "cox.enqueue",
        "cox.dispatch",
        "cox.xla_call",
        "cox.restage",
        "cox.wait",
        "cox.graph.replay",
        "serve.step.call",
        "serve.step.wait",
        "serve.step.emit",
        "serve.prefill.call",
        "serve.prefill.wait",
    }
)
COX_MODULE = "jit_cox_"

# an interval on the profiler's clock, in ns
Interval = Tuple[int, int]


@dataclasses.dataclass
class ProgramTrace:
    # span name -> its intervals inside the window, by start
    spans: Dict[str, List[Interval]]
    # per TPU device plane: (start, end, module name) clipped to the window,
    # by start
    modules: List[List[Tuple[int, int, str]]]

    def mean_ms(self, name: str) -> Optional[float]:
        """Mean length in ms of the spans ``name``; None where there are
        none."""
        found = self.spans.get(name)
        if not found:
            return None
        return 1e-6 * sum(b - a for a, b in found) / len(found)


def from_planes(planes) -> ProgramTrace:
    """The program's spans and modules of a trace's planes: objects with
    ``name`` and ``lines``, each line with ``name`` and ``events``, each
    event with ``name``, ``start_ns`` and ``duration_ns``."""
    spans: Dict[str, List[Interval]] = {}
    windows: List[Interval] = []
    devices = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        found = windows
                    elif e.name in SPANS:
                        found = spans.setdefault(e.name, [])
                    else:
                        continue
                    found.append((int(e.start_ns), int(e.start_ns + e.duration_ns)))
        elif DEVICE_PLANE.match(plane.name):
            devices.append(plane)
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span on the host")
    lo, hi = max(windows)
    inside = {
        name: sorted((a, b) for a, b in found if lo <= a and b <= hi)
        for name, found in spans.items()
    }
    modules = []
    for plane in sorted(devices, key=lambda p: p.name):
        mods = []
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for e in line.events:
                a = max(int(e.start_ns), lo)
                b = min(int(e.start_ns) + int(e.duration_ns), hi)
                if b > a:
                    mods.append((a, b, module_name(e.name)))
        modules.append(sorted(mods))
    return ProgramTrace({k: v for k, v in inside.items() if v}, modules)


@functools.lru_cache(maxsize=1)
def load(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData

    return from_planes(ProfileData.from_file(path).planes)


def of(run) -> Optional[ProgramTrace]:
    """The program trace of a traced run (parsed once), or None for a run
    that was not traced."""
    if run.trace is None:
        return None
    from bench.run import TRACE_DIR

    return load(find_xplane(TRACE_DIR))


def decode_gaps_ms(pt: ProgramTrace) -> List[float]:
    """Host time in ms from the end of one decode step's wait for its
    tokens (``serve.step.wait``) to the start of the next step's wait:
    bookkeeping, the token pipeline, and the next step's call, which the
    chip waits on at batch 1.  Pairs with a prefill between them belong
    to two requests and are left out."""
    waits = pt.spans.get("serve.step.wait", [])
    prefills = [a for a, _ in pt.spans.get("serve.prefill.call", [])]

    def prefill_between(a: int, b: int) -> bool:
        i = bisect.bisect_left(prefills, a)
        return i < len(prefills) and prefills[i] < b

    return [
        1e-6 * (nxt[0] - cur[1])
        for cur, nxt in zip(waits, waits[1:])
        if not prefill_between(cur[1], nxt[0])
    ]


def cox_module_gaps_s(pt: ProgramTrace) -> Optional[float]:
    """Device-clock time between the end of one COX program's module
    (``jit_cox_*``) and the start of the next on the same chip, summed
    over the window, mean over chips; None where no chip ran one."""
    if not pt.modules:
        return None
    total, seen = 0, False
    for mods in pt.modules:
        cox = [(a, b) for a, b, name in mods if name.startswith(COX_MODULE)]
        seen = seen or bool(cox)
        total += sum(max(0, nxt[0] - cur[1]) for cur, nxt in zip(cox, cox[1:]))
    return 1e-9 * total / len(pt.modules) if seen else None
