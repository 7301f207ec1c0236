"""Reduction of a profiler trace to what the per-layer metrics read.

``jax.profiler`` writes an ``.xplane.pb``; ``load`` reads it with
``jax.profiler.ProfileData`` into plain tuples, and the functions below
work on those alone, so that they can be tested on a synthetic trace:

- device programs (the ``XLA Modules`` line of the first device plane),
  found by a substring of their name (``prefill_step``, ``decode_step``);
- device operations (the ``XLA Ops`` line), whose union is the busy time;
- host spans: the benchmark's own (``bench.*`` ``TraceAnnotation``s),
  which group programs by the pump that ran them, and the program's
  (``engine.*``, ``libhas.acquire``); the innermost span open in an idle
  gap of the device says what the host was doing in it.

All times are nanoseconds on the trace's clock.
"""
from __future__ import annotations

import dataclasses
import glob
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]   # (name, start_ns, end_ns)

SPAN_PREFIX = "bench."
# the program's spans inside a pump (``serving/engine.py``, ``libhas.py``)
PROGRAM_SPANS = ("engine.", "libhas.acquire")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    modules: List[Event]     # device programs, by start
    ops: List[Event]         # device operations, by start
    spans: List[Event]       # the kept host spans, by start
    busy: Optional[List[List[float]]] = None   # busy_intervals, once read

    def window(self) -> Tuple[float, float]:
        """The traced window: the ``bench.window`` span."""
        w = next(s for s in self.spans if s[0] == SPAN_PREFIX + "window")
        return w[1], w[2]


def short_name(name: str) -> str:
    """A program's name without its hash (``jit_decode_step``); an
    operation's HLO name without its text (``%fusion.137``)."""
    return name.split(" = ", 1)[0].split("(", 1)[0]


def load(log_dir: str, device_plane: str = "/device:TPU:0") -> Trace:
    """Reads the one ``.xplane.pb`` under ``log_dir``."""
    import jax
    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    modules, ops, spans = [], [], []
    names: Dict[str, str] = {}
    for plane in data.planes:
        if plane.name == device_plane:
            for line in plane.lines:
                dest = {MODULES_LINE: modules, OPS_LINE: ops}.get(line.name)
                if dest is None:
                    continue
                for e in line.events:
                    n = e.name
                    short = names.get(n)
                    if short is None:
                        short = names[n] = short_name(n)
                    dest.append((short, e.start_ns,
                                 e.start_ns + e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(
                                 (SPAN_PREFIX,) + PROGRAM_SPANS))
    for ev in (modules, ops, spans):
        ev.sort(key=lambda e: e[1])
    return Trace(modules, ops, spans)


def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    """Merged, sorted, disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def busy_intervals(tr: Trace) -> List[List[float]]:
    """Union of the device's operations inside the window (its programs
    where the trace has no operation line)."""
    if tr.busy is None:
        lo, hi = tr.window()
        src = tr.ops or tr.modules
        tr.busy = union(clip([(e[1], e[2]) for e in src], lo, hi))
    return tr.busy


def busy_ns(tr: Trace) -> float:
    return sum(b - a for a, b in busy_intervals(tr))


def programs(tr: Trace, key: str) -> List[Event]:
    """Device programs whose name holds ``key``, inside the window. The
    device's clock runs behind the host's: a program dispatched just
    after the window opens can start before it on the trace's clock, so
    one that ends inside the window counts."""
    lo, hi = tr.window()
    return [m for m in tr.modules if key in m[0] and m[2] > lo
            and m[1] < hi]


def span_at(tr: Trace, t: float) -> str:
    """Name of the innermost host span open at ``t``: a program span by
    its full name (``engine.sync``), a benchmark span without its prefix
    (``pump``); the window itself counts as none."""
    best: Optional[Event] = None
    for s in tr.spans:
        if s[1] > t:
            break
        if s[2] >= t and s[0] != SPAN_PREFIX + "window" and (
                best is None or s[1] >= best[1]):
            best = s
    if best is None:
        return "none"
    return best[0].removeprefix(SPAN_PREFIX)


def grouped_by_span(tr: Trace, events: List[Event],
                    span: str) -> List[List[Event]]:
    """``events`` split by the ``bench.<span>`` span that was open when
    each started (the last one that had started); events before the
    first such span are dropped."""
    starts = [s for s in tr.spans if s[0] == SPAN_PREFIX + span]
    groups: Dict[int, List[Event]] = defaultdict(list)
    j = -1
    for e in events:
        while j + 1 < len(starts) and starts[j + 1][1] <= e[1]:
            j += 1
        if j >= 0:
            groups[j].append(e)
    return [groups[k] for k in sorted(groups)]


def gaps_between(events: List[Event]) -> List[float]:
    """Time from the end of each event to the start of the next."""
    return [b[1] - a[2] for a, b in zip(events, events[1:])]


def idle_gaps(tr: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle stretches of the device inside the window,
    as (innermost host span open at its middle, seconds), longest
    first."""
    lo, hi = tr.window()
    busy = busy_intervals(tr)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = sorted(((a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a),
                  key=lambda g: g[0] - g[1])[:n]
    return [(span_at(tr, (a + b) / 2), (b - a) / 1e9) for a, b in gaps]


def top_ops(tr: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """Device operations by their own seconds inside the window (time of
    the operations nested in one, such as a loop's body, is theirs), as
    ``<program>/<operation>``, most first."""
    lo, hi = tr.window()
    tot: Dict[str, float] = defaultdict(float)
    mods = [m for m in tr.modules if m[2] > lo and m[1] < hi]
    j = 0
    stack: List[list] = []        # [label, start, end, nested time]

    def close(ev):
        own = ev[2] - ev[1] - ev[3]
        tot[ev[0]] += own / 1e9
        if stack:
            stack[-1][3] += ev[2] - ev[1]

    for name, a, b in tr.ops:
        if b <= lo or a >= hi:
            continue
        a, b = max(a, lo), min(b, hi)
        while stack and stack[-1][2] <= a:
            close(stack.pop())
        while j + 1 < len(mods) and mods[j + 1][1] <= a:
            j += 1
        prog = mods[j][0] if mods and mods[j][1] <= a < mods[j][2] else "?"
        stack.append([f"{prog}/{name}", a, b, 0.0])
    while stack:
        close(stack.pop())
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]
