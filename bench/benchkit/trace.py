"""Reduction of a profiler trace of the measured window to numbers.

`load(path)` reads the `.xplane.pb` the JAX profiler wrote into plain
events: per chip the operations of its "XLA Ops" line, each named by its
HLO instruction ("closed_call.191") with its opcode ("custom-call"), and
the host's spans. Operations nest there: a `while` spans the operations of
its body. `Reduced` then gives, over the window (the host span
`bench.window` the harness opens at the window's start and closes at its
end):

  busy_s          the union of the chips' operation intervals, in seconds,
                  averaged over the chips;
  op_seconds      per chip, the summed self time of each operation (its
                  time less that of the operations nested in it);
  exposed_collective_s
                  per chip, the time collectives ran with no other
                  operation running on that chip;
  idle_gaps       the device's idle time in the window, by what the host
                  was doing meanwhile (the innermost span of the thread
                  that holds `bench.window`, over each gap's midpoint);
  host_spans      the durations of the host spans of one name.

Events are kept as (name, start_ns, end_ns, opcode) tuples; `from_events`
builds the same structure from a recorded list, which is how the reduction
is tested.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int, str]
WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
_COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all|send|recv)")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def parse_op(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an "XLA Ops" event, whose name is the
    HLO instruction's text: "%fusion.3 = f32[8]{0} fusion(...), ..." """
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text, ""
    m = _OPCODE.search(" " + rest)
    return head.lstrip("%"), m.group(1) if m else ""


def load(path: str) -> Dict:
    """{"devices": {chip: [event]}, "host": [(name, start, end, thread)]}"""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    host = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs = []
                    for e in line.events:
                        name, op = parse_op(e.name)
                        evs.append((name, int(e.start_ns),
                                    int(e.start_ns + e.duration_ns), op))
                    devices[int(m.group(1))] = evs
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns), line.name)
                            for e in line.events)
    return {"devices": devices, "host": host}


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def measure(intervals: Sequence[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Tuple[int, int]], b: Sequence[Tuple[int, int]]
             ) -> List[Tuple[int, int]]:
    """Parts of the (merged, sorted) intervals `a` not covered by the
    (merged, sorted) intervals `b`."""
    out = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def _clip(events: Iterable[Event], lo: int, hi: int) -> List[Event]:
    return [(n, max(a, lo), min(b, hi), op) for n, a, b, op in events
            if b > lo and a < hi]


def self_times(events: Sequence[Event]) -> List[float]:
    """Each event's duration less that of the events nested directly in
    it (same line), in ns, in the order given."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [events[i][2] - events[i][1] for i in range(len(events))]
    stack: List[int] = []
    for i in order:
        a, b = events[i][1], events[i][2]
        while stack and events[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= events[stack[-1]][2]:
            own[stack[-1]] -= b - a
        stack.append(i)
    return own


class Reduced:
    def __init__(self, devices: Dict[int, List[Event]],
                 host: List[Tuple[str, int, int, str]], chips: int):
        spans = [h for h in host if h[0] == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        self.lo, self.hi = spans[0][1], spans[0][2]
        chip_ids = sorted(devices)[:chips]
        if len(chip_ids) < chips or not any(devices[c] for c in chip_ids):
            raise ValueError(f"trace holds operations of chips {sorted(devices)}"
                             f", the run used {chips}")
        self.thread = spans[0][3]
        self.ops = {c: _clip(devices[c], self.lo, self.hi) for c in chip_ids}
        self.host = [(n, max(a, self.lo), min(b, self.hi), t)
                     for n, a, b, t in host
                     if n != WINDOW_SPAN and b > self.lo and a < self.hi]
        self.busy = {c: union((a, b) for _, a, b, _ in evs)
                     for c, evs in self.ops.items()}
        self._self = {c: self_times(evs) for c, evs in self.ops.items()}

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        return sum(measure(b) for b in self.busy.values()) / len(self.busy) \
            / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, chip: int) -> Dict[str, float]:
        """Self time of each operation name on `chip`, in seconds."""
        out: Dict[str, float] = collections.defaultdict(float)
        for ev, own in zip(self.ops[chip], self._self[chip]):
            out[ev[0]] += own / 1e9
        return dict(out)

    def op_counts(self, chip: int) -> Dict[str, int]:
        return dict(collections.Counter(ev[0] for ev in self.ops[chip]))

    def opcodes(self, chip: int) -> Dict[str, str]:
        return {ev[0]: ev[3] for ev in self.ops[chip]}

    def exposed_collective_s(self, chip: int) -> float:
        """Time collectives ran on `chip` with no other operation running,
        apart from the loops and calls that contain them."""
        evs = self.ops[chip]
        coll = union((a, b) for _, a, b, op in evs if _COLLECTIVE.match(op))
        other = union((a, b) for _, a, b, op in evs
                      if not _COLLECTIVE.match(op)
                      and op not in ("while", "call", "conditional"))
        return measure(subtract(coll, other)) / 1e9

    def host_spans(self, name: str) -> List[float]:
        return [(b - a) / 1e9 for n, a, b, _ in self.host if n == name]

    def idle_gaps(self, chip: Optional[int] = None) -> Dict[str, float]:
        """Idle seconds of `chip` (the first by default) in the window, by
        the innermost host span over each gap's midpoint."""
        chip = min(self.busy) if chip is None else chip
        gaps = subtract([(self.lo, self.hi)], self.busy[chip])
        spans = sorted((h for h in self.host if h[3] == self.thread),
                       key=lambda h: h[1])
        starts = [h[1] for h in spans]
        out: Dict[str, float] = collections.defaultdict(float)
        for a, b in gaps:
            mid = (a + b) // 2
            i = bisect.bisect_right(starts, mid)
            best = None
            for n, s, e, _ in spans[:i]:
                if e > mid and (best is None or e - s < best[1]):
                    best = (n, e - s)
            out[best[0] if best else "no host span"] += (b - a) / 1e9
        return dict(out)

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        chips = sorted(self.ops)
        tot: Dict[str, float] = collections.defaultdict(float)
        for c in chips:
            codes = self.opcodes(c)
            for n, s in self.op_seconds(c).items():
                tot[f"{n} {codes[n]}".strip()] += s / len(chips)
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps().items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def reduce_file(path: str, chips: int) -> Reduced:
    if path is None:
        raise ValueError("the profiler wrote no trace")
    t = load(path)
    return Reduced(t["devices"], t["host"], chips)


def from_events(recorded: Dict, chips: int) -> Reduced:
    """The same reduction over a recorded trace: {"devices": {chip:
    [[name, start_ns, end_ns, opcode], ...]}, "host": [[name, start, end,
    thread], ...]} (chip keys may be strings, as JSON writes them)."""
    devices = {int(c): [tuple(e) for e in evs]
               for c, evs in recorded["devices"].items()}
    host = [tuple(h) for h in recorded["host"]]
    return Reduced(devices, host, chips)


def custom_calls(hlo_text: str) -> List[str]:
    """Instruction names of the Mosaic (Pallas) kernels in a compiled
    program's HLO text."""
    names = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
            if m:
                names.append(m.group(1))
    return names
