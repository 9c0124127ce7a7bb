"""Reduction of one profiler trace (``.xplane.pb``) to device numbers.

The harness wraps its measured window in the host span ``bench.window``
and its own calls in ``bench.step`` (one ``ContinuousBatchingEngine
.step()``), ``bench.submit`` and ``bench.wait`` (the generator idle until
the next request is due).  From the trace this module takes, inside that
window:

  * busy: the union of the intervals in which an operation ran on each
    device (line ``XLA Ops`` of the ``/device:TPU:<n>`` planes), averaged
    over the devices;
  * per-family kernel time: each device operation is matched against the
    name table (``trace_names.json``), first match wins; unmatched ones
    count under their own name only;
  * ``device_ops``: the operations that took most time, by family or by
    HLO opcode and result type (control flow, which holds other
    operations, is left out);
  * ``idle_gaps``: the device's idle time inside the window, summed by
    what the host was doing in each gap (the harness span covering the
    gap's middle, or ``host:other``).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.step", "bench.submit", "bench.wait")
CONTAINERS = ("while", "conditional", "call")
TOP = 10

Interval = Tuple[int, int]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over devices
    devices: int
    family_s: Dict[str, float]          # summed over devices
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def load_names(path: Optional[str] = None) -> List[Tuple[str, re.Pattern]]:
    path = path or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "trace_names.json")
    with open(path) as f:
        table = json.load(f)
    return [(row["family"], re.compile(row["pattern"]))
            for row in table["families"]]


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} xplane files under {log_dir}")
    return found[0]


def classify(name: str, names) -> Optional[str]:
    for fam, pat in names:
        if pat.search(name):
            return fam
    return None


def op_label(name: str) -> str:
    """``%copy.67 = bf16[28,1792]{...} copy(...)`` -> ``copy bf16[28,1792]``."""
    head, _, rest = name.partition(" = ")
    op = re.sub(r"\.\d+$", "", head.lstrip("%"))
    rtype = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{op} {rtype}".strip()[:96]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: int, e: int, w: Interval) -> Optional[Interval]:
    s, e = max(s, w[0]), min(e, w[1])
    return (s, e) if e > s else None


def reduce(path: str, names=None) -> TraceSummary:
    from jax.profiler import ProfileData
    names = load_names() if names is None else names
    data = ProfileData.from_file(path)
    window: Optional[Interval] = None
    host: List[Tuple[int, int, str]] = []
    device_lines = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_lines.append(list(
                        (e.name, int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                    elif e.name in HOST_SPANS:
                        host.append((int(e.start_ns),
                                     int(e.start_ns + e.duration_ns), e.name))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN} span in {path}")
    if not device_lines:
        raise ValueError(f"no device operations in {path}")

    family_ns: Dict[str, int] = defaultdict(int)
    op_ns: Dict[str, int] = defaultdict(int)
    busy_ns = 0
    gaps: Dict[str, int] = defaultdict(int)
    host.sort()
    starts = [s for s, _, _ in host]
    # Names repeat across steps: classify each distinct name once.
    kind: Dict[str, Tuple[Optional[str], Optional[str]]] = {}
    for events in device_lines:
        spans = []
        for name, s, e in events:
            c = _clip(s, e, window)
            if c is None:
                continue
            spans.append(c)
            k = kind.get(name)
            if k is None:
                fam = classify(name, names)
                label = op_label(name)
                if fam is None and label.startswith(CONTAINERS):
                    label = None
                k = kind[name] = (fam, fam or label)
            fam, label = k
            if fam is not None:
                family_ns[fam] += c[1] - c[0]
            if label is not None:
                op_ns[label] += c[1] - c[0]
        busy = union(spans)
        busy_ns += sum(e - s for s, e in busy)
        edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps[_host_at((s + e) // 2, host, starts)] += e - s

    n = len(device_lines)
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]
    return TraceSummary(
        window_s=(window[1] - window[0]) / 1e9,
        busy_s=busy_ns / n / 1e9,
        devices=n,
        family_s={k: v / 1e9 for k, v in family_ns.items()},
        device_ops=[(k, v / 1e9) for k, v in top_ops],
        idle_gaps=[(k, v / 1e9 / n) for k, v in top_gaps])


def _host_at(t: int, host: List[Tuple[int, int, str]], starts: List[int]
             ) -> str:
    """The harness span covering time ``t`` (the spans never overlap)."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and host[i][1] >= t:
        return f"host:{host[i][2]}"
    return "host:other"
