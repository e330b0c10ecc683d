"""From a ``jax.profiler`` trace to the numbers the per-layer metrics read.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per operation
run on the chip, named by its HLO instruction (``%while.592 = ...``), with
the operations of a loop's body nested inside the loop's event.  The
instruction's name-scope path (``jit(round_step)/fedlt.local_train/...``)
is not in the trace: it is read from the compiled program's HLO text,
whose instructions carry it as ``metadata={op_name=...}``.  The host
plane's thread lines hold the spans opened with
``jax.profiler.TraceAnnotation``, among them the benchmark's window,
``chipbench.window``, which bounds what is counted.

Device times are moved onto the host's clock first (see
``_clock_offset``).  Only top-level operations count (a loop's event
already holds its body).
Busy time is the union of their intervals inside the window, averaged
over the chips; a scope's time is the sum of the durations of the
operations whose path holds that scope; a Pallas kernel's time the sum
over its custom calls, which carry the kernel's name.  Each idle gap is
labelled with the innermost span open at its middle on the thread that
opened the window.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import shutil
from typing import List, Optional, Tuple

#: the host span that bounds the measured window
WINDOW = "chipbench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Op:
    start: float        # seconds
    end: float
    instr: str          # the HLO instruction's name
    path: str           # its name-scope path, or its name where unknown
    device: int
    custom_call: bool = False


@dataclasses.dataclass
class Span:
    start: float
    end: float
    name: str
    depth: int = 0


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:           # a stat the reader cannot turn into Python
        return {}


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"")


def hlo_op_names(texts) -> dict:
    """HLO instruction name → its name-scope path, from compiled HLO
    texts (``compiled.as_text()``)."""
    out = {}
    for text in texts:
        for line in text.splitlines():
            m = _INSTR.match(line)
            if m:
                out.setdefault(m.group(1), m.group(2))
    return out


def _instr(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _scope_re(scope: str):
    return re.compile(r"(^|[/(])" + re.escape(scope) + r"($|[/)])")


@dataclasses.dataclass
class Summary:
    window: Tuple[float, float]
    ops: List[Op]
    spans: List[Span]
    n_devices: int

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _intervals(self, device: int) -> list:
        iv = sorted((o.start, o.end) for o in self.ops if o.device == device)
        merged = []
        for s, e in iv:
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return merged

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        devices = sorted({o.device for o in self.ops})
        if not devices:
            return 0.0
        total = sum(e - s for d in devices for s, e in self._intervals(d))
        return total / max(self.n_devices, len(devices))

    def scope_s(self, scope: str) -> float:
        """Device seconds of the operations under a name scope (the
        scope or any scope nested in it), averaged over the chips."""
        pat = _scope_re(scope)
        s = sum(o.end - o.start for o in self.ops if pat.search(o.path))
        return s / max(self.n_devices, 1)

    def kernel_s(self, kernel: str) -> float:
        """Device seconds of a Pallas kernel: the custom calls named after
        it, averaged over the chips."""
        pat = re.compile(re.escape(kernel) + r"(\.\d+)?$")
        s = sum(o.end - o.start for o in self.ops
                if o.custom_call and pat.match(o.instr))
        return s / max(self.n_devices, 1)

    def gaps(self, device: Optional[int] = None) -> list:
        """``(label, seconds)`` of each stretch of the window in which the
        chip ran nothing, labelled by the innermost host span open at its
        middle."""
        devices = sorted({o.device for o in self.ops})
        if not devices:
            return [("no device operation", self.window_s)]
        d = devices[0] if device is None else device
        t = self.window[0]
        stretches = []
        for s, e in self._intervals(d) + [[self.window[1], self.window[1]]]:
            if s > t:
                stretches.append((t, s))
            t = max(t, e)
        labels = self._labels([(a + b) / 2 for a, b in stretches])
        return [(label, b - a) for label, (a, b) in zip(labels, stretches)]

    def _labels(self, times: list) -> list:
        """The innermost span open at each of the (rising) ``times``: one
        sweep over the spans in order of start, keeping those still open
        on a stack (spans of one thread nest)."""
        spans = sorted((sp for sp in self.spans if sp.name != WINDOW),
                       key=lambda sp: (sp.start, -sp.end))
        out, stack, i = [], [], 0
        for t in times:
            while i < len(spans) and spans[i].start <= t:
                while stack and stack[-1].end < spans[i].start:
                    stack.pop()
                stack.append(spans[i])
                i += 1
            while stack and stack[-1].end < t:
                stack.pop()
            out.append(stack[-1].name if stack else "no host span open")
        return out

    def top_ops(self, n: int = 10) -> list:
        """The device operations that took most time, grouped by their
        innermost ``fedlt.`` scope and the kind of instruction."""
        acc = collections.Counter()
        for o in self.ops:
            scopes = re.findall(r"fedlt\.[\w.]+", o.path)
            kind = re.sub(r"(\.\d+)+$", "", o.instr)
            acc[f"{scopes[-1] if scopes else 'other'}:{kind}"] += o.end - o.start
        k = max(self.n_devices, 1)
        return [[name, s / k] for name, s in acc.most_common(n)]

    def breakdown(self) -> dict:
        longest = sorted(self.gaps(), key=lambda g: -g[1])
        return {"device_ops": self.top_ops(10),
                "idle_gaps": [[label, s] for label, s in longest[:10]]}


def find_xplane(trace_dir) -> str:
    paths = sorted(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_file(path, hlo_texts=()) -> Summary:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)), hlo_texts)


def reduce_dir(trace_dir, hlo_texts=()) -> Summary:
    return reduce_file(find_xplane(trace_dir), hlo_texts)


def _top_level(events) -> list:
    """``(start_ns, end_ns, event)`` of the events not nested in an
    earlier one, in time order; one that starts inside an earlier event
    and ends after it keeps only the part after."""
    out, end = [], float("-inf")
    for ev in sorted(events, key=lambda e: (e.start_ns, -e.duration_ns)):
        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
        if e <= end:
            continue
        out.append((max(s, end), e, ev))
        end = e
    return out


def _host_spans(line) -> list:
    spans, stack = [], []
    for ev in sorted(line.events, key=lambda e: (e.start_ns, -e.duration_ns)):
        s = ev.start_ns * 1e-9
        e = s + ev.duration_ns * 1e-9
        while stack and stack[-1] <= s:
            stack.pop()
        spans.append(Span(s, e, ev.name, len(stack)))
        stack.append(e)
    return spans


def _program(name: str) -> str:
    """The jitted function's name, from a device module event
    (``jit_round_step(123)``) or a host dispatch span
    (``PjitFunction(jit(round_step))``, ``PjitFunction(round)``)."""
    if name.startswith("PjitFunction("):
        inner = name[len("PjitFunction("):-1]
        return inner[4:-1] if inner.startswith("jit(") else inner
    base = name.split("(", 1)[0]
    return base[4:] if base.startswith("jit_") else base


def _clock_offset(modules: list, spans: list) -> float:
    """Seconds to add to device times to put them on the host's clock.

    The two clocks of a trace can differ by a millisecond or more.  Each
    run of a program on the chip starts after the host dispatched it, so
    pairing the last dispatches of each program with its last runs (the
    window ends with the device waited for) bounds the offset from below;
    the largest of these bounds is taken."""
    dispatches = collections.defaultdict(list)
    for sp in sorted(spans, key=lambda x: x.start):
        if sp.name.startswith("PjitFunction("):
            d = dispatches[_program(sp.name)]
            if not d or sp.start >= d[-1].end:          # nested duplicates
                d.append(sp)
    runs = collections.defaultdict(list)
    for name, start in sorted(modules, key=lambda m: m[1]):
        runs[_program(name)].append(start)
    bounds = [h.start - d for prog, hs in dispatches.items()
              for h, d in zip(reversed(hs), reversed(runs.get(prog, [])))]
    return max(bounds) if bounds else 0.0


def reduce_profile(pd, hlo_texts=()) -> Summary:
    names = hlo_op_names(hlo_texts)
    ops, lines, modules = [], [], []
    n_devices = 0
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            n_devices += 1
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules += [(ev.name, ev.start_ns * 1e-9) for ev in line.events]
                if line.name != OPS_LINE:
                    continue
                for s, e, ev in _top_level(line.events):
                    instr = _instr(ev.name)
                    ops.append(Op(s * 1e-9, e * 1e-9, instr,
                                  names.get(instr, instr), dev,
                                  "custom-call(" in ev.name))
        elif plane.name.startswith("/host:"):
            lines.extend(_host_spans(line) for line in plane.lines)
    window = [sp for spans in lines for sp in spans if sp.name == WINDOW]
    if window:
        w = (window[0].start, window[0].end)
        spans = next(sp for sp in lines if any(x.name == WINDOW for x in sp))
    else:
        spans = [sp for spans in lines for sp in spans]
    shift = _clock_offset(modules, spans)
    ops = [dataclasses.replace(o, start=o.start + shift, end=o.end + shift)
           for o in ops]
    if not window:
        w = ((min(o.start for o in ops), max(o.end for o in ops)) if ops
             else (0.0, 0.0))
    clipped = [dataclasses.replace(o, start=max(o.start, w[0]), end=min(o.end, w[1]))
               for o in ops if o.end > w[0] and o.start < w[1]]
    inside = [sp for sp in spans if sp.end > w[0] and sp.start < w[1]]
    return Summary(window=w, ops=clipped, spans=inside, n_devices=n_devices)


def clear(trace_dir) -> None:
    shutil.rmtree(str(trace_dir), ignore_errors=True)
