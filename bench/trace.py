"""Reduce a profiler trace to the device's busy time, kernel calls and a breakdown.

Reads the ``.xplane.pb`` the JAX profiler writes, with nothing but JAX's
``ProfileData``:

* the traced window runs from the start of the harness's first host span
  (``step``, ``submit``, ``wait``: ``jax.profiler.TraceAnnotation``s the
  harness puts around its calls into the engine) to the end of its last;
* busy time is the union of the intervals of the device's operations
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped to the
  window and averaged over the devices;
* each call of a Pallas kernel (an op whose HLO text calls the
  ``tpu_custom_call`` target, named by the ``pallas_call``'s ``name``) is
  kept with its device time and its operand and output shapes, parsed from
  the HLO text the event carries, so that a metric reader can count its
  operations and bytes;
* the breakdown lists the operations that took the most device time and
  the longest idle gaps, each gap labelled by the host span it lies in.
"""

from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_SPANS = ("step", "submit", "wait")
PALLAS_TARGET = "tpu_custom_call"
TOP = 10

_SUFFIX = re.compile(r"\.\d+$")
_SHAPE = re.compile(r"[a-z]+\d*\[([\d,]*)\]")


def op_name(event_name: str) -> str:
    """The instruction an op event names, without its ``.<n>`` suffix.

    On a TPU the event's name is the HLO text of the instruction,
    ``%os_segment.1 = f32[...] custom-call(...), ...``.
    """
    name = event_name
    if name.startswith("%") and " = " in name:
        name = name[1 : name.index(" = ")]
    return _SUFFIX.sub("", name)


def hlo_shapes(text: str) -> Optional[Tuple[List[tuple], tuple]]:
    """(operand shapes, output shape) of one HLO instruction's text, e.g.
    ``%k.1 = f32[2,3]{1,0:T(8,128)} custom-call(f32[4]{0} %a, f32[5]{0} %b), ...``."""
    if " = " not in text:
        return None
    rhs = text.split(" = ", 1)[1]
    if rhs.startswith("("):  # a tuple: its first element stands for the output
        i = _close(rhs, 0, "(", ")") + 1
        out = _SHAPE.search(rhs, 0, i)
    else:
        out = _SHAPE.match(rhs)
        i = out.end() if out else 0
        if i < len(rhs) and rhs[i] == "{":  # the output's layout
            i = _close(rhs, i, "{", "}") + 1
    if out is None:
        return None
    call = rhs.find("(", i)
    if call < 0:
        return None
    args = rhs[call + 1 : _close(rhs, call, "(", ")")]
    operands = [_dims(m.group(1)) for m in _SHAPE.finditer(args)]
    return operands, _dims(out.group(1))


def _close(text: str, i: int, open_: str, close: str) -> int:
    """Index of the bracket that closes the one at ``i``."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] == open_:
            depth += 1
        elif text[j] == close:
            depth -= 1
            if depth == 0:
                return j
    return len(text)


def _dims(s: str) -> tuple:
    return tuple(int(d) for d in s.split(",") if d)


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def reduce(profile) -> dict:
    """The reduction of one ``ProfileData``; see the module docstring."""
    spans: List[Tuple[int, int, str]] = []
    devices = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in HOST_SPANS:
                    spans.append((int(e.start_ns), int(e.start_ns + e.duration_ns), e.name))
    if not spans:
        raise ValueError("the trace holds none of the harness's host spans")
    w0 = min(s[0] for s in spans)
    w1 = max(s[1] for s in spans)

    busy_total = 0.0
    op_time: Dict[str, float] = {}
    kernels: Dict[str, list] = {}
    shapes_of: Dict[str, Optional[tuple]] = {}  # HLO text -> shapes, parsed once
    first_union = None
    for plane in devices:
        ops = [ln for ln in plane.lines if ln.name == OPS_LINE]
        intervals = []
        for line in ops:
            for e in line.events:
                a, b = int(e.start_ns), int(e.start_ns + e.duration_ns)
                a, b = max(a, w0), min(b, w1)
                if b <= a:
                    continue
                intervals.append((a, b))
                name = op_name(e.name)
                op_time[name] = op_time.get(name, 0.0) + (b - a) * 1e-9
                if PALLAS_TARGET in e.name:
                    if e.name not in shapes_of:
                        shapes_of[e.name] = hlo_shapes(e.name)
                    shapes = shapes_of[e.name]
                    if shapes is not None:
                        kernels.setdefault(name, []).append(((b - a) * 1e-9, shapes[0], shapes[1]))
        merged = _union(intervals)
        if first_union is None:
            first_union = merged
        busy_total += sum(b - a for a, b in merged) * 1e-9
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    busy = busy_total / len(devices)

    gaps = []
    prev = w0
    for a, b in (first_union or []) + [(w1, w1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    labelled = []
    for a, b in gaps:
        best, label = 0, "none"
        for s0, s1, name in spans:
            overlap = min(b, s1) - max(a, s0)
            if overlap > best:
                best, label = overlap, name
        labelled.append([label, (b - a) * 1e-9])
    labelled.sort(key=lambda g: -g[1])
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy,
        "window_s": (w1 - w0) * 1e-9,
        "kernels": kernels,
        "breakdown": {
            "device_ops": [[k, v] for k, v in top_ops],
            "idle_gaps": labelled[:TOP],
        },
    }


def reduce_dir(trace_dir: str) -> dict:
    """``reduce`` of the newest trace the profiler wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise ValueError(f"no trace under {trace_dir}")
    return reduce(ProfileData.from_file(str(paths[-1])))
