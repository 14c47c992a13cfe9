"""From a profiler trace of the measured window to device numbers.

The window is marked in the trace itself by two host annotations,
``chipbench.window_open`` and ``chipbench.window_close``.  Inside it:

* busy time: the union of the intervals in which an operation ran on the
  device (the ``XLA Ops`` line of each ``/device:`` plane), averaged over
  the devices used; the idle share is 1 - busy / window;
* device time per operation name, summed, for ``breakdown.device_ops``;
* device time per scope of the JAX name stack each operation was traced
  under (``jax.named_scope``): the union of the intervals of the
  operations under that scope, so that a ``while`` and the operations
  inside it count once;
* every idle gap between device operations, named by what the host was
  doing in it: the shortest host event that covers at least half of the
  gap, else the one that overlaps it most, else ``"unattributed"``.

``load_events`` reads an ``.xplane.pb`` with nothing but JAX; ``summarize``
works on plain tuples, so the test checks it on a small recorded trace.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

OPEN, CLOSE = "chipbench.window_open", "chipbench.window_close"
TOP = 10
# the stats of a device operation that may carry its JAX name stack, in
# the order they are tried
NAME_STACK_STATS = ("tf_op", "op_name")

# host events: (name, start_ns, duration_ns); device operations add the
# name stack: (name, start_ns, duration_ns, name_stack)
Event = Tuple[str, float, float]


@dataclasses.dataclass
class Events:
    """The events of one trace: device operations per device, host events
    (annotations and runtime activity), all on one clock in ns."""

    device_ops: Dict[str, List[Event]]
    host: List[Event]

    def to_json(self) -> dict:
        return {"device_ops": self.device_ops, "host": self.host}

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        return cls({k: [tuple(e) for e in v]
                    for k, v in d["device_ops"].items()},
                   [tuple(e) for e in d["host"]])


@dataclasses.dataclass
class Summary:
    busy_s: float
    window_s: float
    # per operation name and per scope of the name stack, device seconds
    # in the window (summed over devices)
    op_seconds: Dict[str, float]
    scope_seconds: Dict[str, float]
    breakdown: dict

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def profiler_options():
    """Host annotations and runtime events; no Python call tracing, which
    would slow the host it is meant to observe."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def op_name(hlo: str) -> str:
    """An operation's name from its event name, which on a TPU is the HLO
    instruction: ``%fusion.12 = bf16[...] fusion(...)`` gives ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def scopes(name_stack: str) -> List[str]:
    """The scopes of a name stack, outermost first, without the operation
    itself and with transformations unwrapped:
    ``jit(scan)/while/body/transpose(jvp(attn))/dot_general`` gives
    ``scan``, ``while``, ``body``, ``attn``."""
    out = []
    for part in name_stack.split("/")[:-1]:
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        if part and part not in out:
            out.append(part)
    return out


def _name_stack(stats) -> str:
    found = dict(stats)
    for key in NAME_STACK_STATS:
        if found.get(key):
            return str(found[key])
    return ""


def load_events(path: str) -> Events:
    """Device operations, each with its name stack, and host events of an
    ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            line = lines.get("XLA Ops")
            if line is None:
                continue
            device_ops[plane.name] = [
                (op_name(e.name), float(e.start_ns), float(e.duration_ns),
                 _name_stack(e.stats))
                for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events)
    return Events(device_ops, host)


def save_events(events: Events, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events.to_json(), f)


def read_events(path: str) -> Events:
    with gzip.open(path, "rt") as f:
        return Events.from_json(json.load(f))


def _window(host: Sequence[Event]) -> Tuple[float, float]:
    opens = [s for n, s, _ in host if n == OPEN]
    closes = [s for n, s, _ in host if n == CLOSE]
    if not opens or not closes:
        raise ValueError("the trace has no window marks")
    lo, hi = min(opens), max(closes)
    if hi <= lo:
        raise ValueError("the window closes before it opens")
    return lo, hi


def _union(intervals: List[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _name_gap(a: float, b: float, host: Sequence[Event]) -> str:
    best_cover: Optional[Tuple[float, str]] = None
    best_overlap: Optional[Tuple[float, str]] = None
    for name, s, d in host:
        if name in (OPEN, CLOSE):
            continue
        ov = min(b, s + d) - max(a, s)
        if ov <= 0:
            continue
        if ov >= 0.5 * (b - a) and (best_cover is None or d < best_cover[0]):
            best_cover = (d, name)
        if best_overlap is None or ov > best_overlap[0]:
            best_overlap = (ov, name)
    if best_cover is not None:
        return best_cover[1]
    return best_overlap[1] if best_overlap is not None else "unattributed"


def summarize(events: Events, devices: Optional[Sequence[str]] = None
              ) -> Summary:
    """Reduce one traced window to busy time, device time per operation
    and per scope, and the breakdown."""
    lo, hi = _window(events.host)
    planes = sorted(devices or events.device_ops)
    if not planes:
        raise ValueError("the trace has no device operations")
    busy = 0.0
    op_ns: Dict[str, float] = defaultdict(float)
    scope_ns: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for plane in planes:
        spans = []
        by_scope: Dict[str, list] = defaultdict(list)
        for name, s, d, *stack in events.device_ops.get(plane, []):
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                spans.append((a, b))
                op_ns[name] += b - a
                for scope in scopes(stack[0] if stack else ""):
                    by_scope[scope].append((a, b))
        for scope, intervals in by_scope.items():
            scope_ns[scope] += sum(b - a for a, b in _union(intervals))
        merged = _union(spans)
        busy += sum(b - a for a, b in merged)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [e for e in events.host if e[1] < hi and e[1] + e[2] > lo]
    idle = [[_name_gap(a, b, host), (b - a) / 1e9] for a, b in gaps[:TOP]]
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(
        busy_s=busy / len(planes) / 1e9, window_s=(hi - lo) / 1e9,
        op_seconds={k: v / 1e9 for k, v in op_ns.items()},
        scope_seconds={k: v / 1e9 for k, v in scope_ns.items()},
        breakdown={"device_ops": [[k, v / 1e9] for k, v in ops],
                   "idle_gaps": idle})
