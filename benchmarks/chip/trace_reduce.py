"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's quantities.

- device busy time: the union of the intervals in which an operation ran
  on each device (the ``XLA Ops`` line of each ``/device:`` plane),
  averaged over the devices, and the traced window's length;
- time per kind of operation (the HLO instruction's name without its
  number: ``qmatmul``, ``int_paged_decode_attention``, ``fusion``, ...),
  and per jitted program (the ``XLA Modules``
  line: ``jit_step_fn``, ``jit_admit_fn``, ...) with its executions;
- the idle gaps between busy intervals, each labelled with the innermost
  of the harness's own host spans (``bench.*``) that holds the gap's
  middle.

Run as a script on a trace file it prints the reduction as JSON.
"""
from __future__ import annotations

import bisect
import glob
import json
import re
import sys
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
# Control flow whose events enclose the operations they run: left out of
# the ranking of operations (their time is their body's), kept in busy.
CONTAINERS = ("while", "conditional", "call")


def kind(event_name: str) -> str:
    """``%qmatmul.91 = f32[...] custom-call(...)`` -> ``qmatmul``."""
    short = event_name.split(" ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", short)


def find(trace_dir: str) -> str:
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file under {trace_dir}, "
                           f"found {paths}")
    return paths[0]


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _events(path):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
                     for line in plane.lines}
            if lines.get(OPS_LINE):
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return devices, host


def reduce(path: str, t_on: float | None = None,
           t_off: float | None = None) -> dict:
    """The trace's numbers (seconds).  ``t_on``/``t_off``: the host clock
    when tracing started and stopped, whose difference is the window."""
    devices, host = _events(path)
    if not devices:
        raise RuntimeError(f"no device operations in {path}")
    op_s: dict[str, float] = defaultdict(float)
    op_n: dict[str, int] = defaultdict(int)
    mod_s: dict[str, float] = defaultdict(float)
    mod_n: dict[str, int] = defaultdict(int)
    busy = []
    gaps = []
    spans = sorted(host, key=lambda h: h[1])
    for lines in devices.values():
        ops = lines[OPS_LINE]
        for name, start, dur in ops:
            k = kind(name)
            op_s[k] += dur * 1e-9
            op_n[k] += 1
        for name, start, dur in lines.get(MODULES_LINE, []):
            key = name.split("(")[0]
            mod_s[key] += dur * 1e-9
            mod_n[key] += 1
        merged = _union((s, s + d) for _, s, d in ops)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        starts = [h[1] for h in spans]
        longest = max((h[2] for h in spans), default=0)
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            mid = (e0 + s1) / 2
            label, best = "no harness span", None
            lo = bisect.bisect_left(starts, mid - longest)
            for name, hs, hd in spans[lo:bisect.bisect_right(starts, mid)]:
                if hs + hd >= mid and (best is None or hd < best):
                    best, label = hd, name
            gaps.append((label, (s1 - e0) * 1e-9))
    n_dev = len(devices)
    first = min(s for lines in devices.values() for _, s, _ in lines[OPS_LINE])
    last = max(s + d for lines in devices.values()
               for _, s, d in lines[OPS_LINE])
    window = (t_off - t_on) if t_on is not None else (last - first) * 1e-9
    by_label: dict[str, float] = defaultdict(float)
    for label, g in gaps:
        by_label[label] += g / n_dev
    return {
        "busy_s": sum(busy) / n_dev,
        "window_s": window,
        "span_s": (last - first) * 1e-9,
        "devices": n_dev,
        "ops": {k: [op_s[k] / n_dev, op_n[k] / n_dev] for k in op_s},
        "modules": {k: [mod_s[k] / n_dev, mod_n[k] / n_dev] for k in mod_s},
        "top_ops": sorted(([k, op_s[k] / n_dev] for k in op_s
                           if k not in CONTAINERS), key=lambda x: -x[1])[:10],
        "top_gaps": sorted(([lbl, g / n_dev] for lbl, g in gaps),
                           key=lambda x: -x[1])[:10],
        "gaps_by_span": dict(by_label),
    }


def op_seconds(reduced: dict, op_kind: str) -> tuple[float, float]:
    """(seconds, executions) of one kind of operation."""
    sec, cnt = reduced["ops"].get(op_kind, (0.0, 0.0))
    return sec, cnt


def module_seconds(reduced: dict, name: str) -> tuple[float, float]:
    """(seconds, executions) of one jitted program."""
    sec, cnt = reduced["modules"].get(name, (0.0, 0.0))
    return sec, cnt


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1]), indent=1))
