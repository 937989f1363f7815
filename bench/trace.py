"""Reduction of a profiler trace to the benchmark's device numbers.

A traced run records the JAX profiler over a steady window and wraps each
harness step in a `jax.profiler.TraceAnnotation` named `bench.<step>`.
`reduce_trace` reads the `.xplane.pb` file with `jax.profiler.ProfileData`
and returns, per device: the union of the intervals in which an XLA
operation ran (busy time) inside the window, the device time of each
Pallas kernel, the operations that took most time, and the longest idle
gaps with the harness step the host was in when each began.

The window runs from the start of the first `bench.` span to the end of
the last.  Device operations are the events of each TPU plane's `XLA Ops`
line; an operation's own time leaves out the operations nested in it (a
loop spans its body).  Kernels are found by joining the trace's operation
names to the compiled program's `tpu_custom_call` instructions
(`kernel_instructions`), each named by the jit wrapper its `op_name`
metadata gives.
"""
from __future__ import annotations

import collections
import glob
import gzip
import os
import re
from typing import Dict, List

SPAN_PREFIX = "bench."
TOP = 10


def kernel_instructions(hlo_text: str) -> Dict[str, str]:
    """{instruction name: jit wrapper} of each `tpu_custom_call` in the
    compiled HLO text (the innermost `jit(...)` of its `op_name`)."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
        op = re.search(r'op_name="([^"]*)"', line)
        wrappers = re.findall(r"jit\((\w+)\)", op.group(1) if op else "")
        if name and wrappers:
            out[name.group(1)] = wrappers[-1]
    return out


def op_paths(hlo_text: str) -> Dict[str, str]:
    """{instruction name: the tail of its `op_name` metadata} of every
    instruction of the compiled HLO text: where in the JAX program it
    comes from, for naming the device operations of a breakdown."""
    out = {}
    for line in hlo_text.splitlines():
        name = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
        op = re.search(r'op_name="([^"]*)"', line)
        if name and op:
            out[name.group(1)] = "/".join(op.group(1).split("/")[-3:])
    return out


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file under {directory}, found {paths}")
    return paths[0]


def _union(intervals: List[tuple]) -> List[tuple]:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def op_name(event_name: str) -> str:
    """The HLO instruction name of an `XLA Ops` event, whose name is the
    instruction's text (`%fusion.12 = f32[...] fusion(...)`)."""
    m = re.match(r"\s*%?([\w.\-]+)\s*=", event_name)
    return m.group(1) if m else event_name


def device_planes(data):
    return [p for p in data.planes if p.name.startswith("/device:TPU:")]


def _self_times(events):
    """{event index: duration less its nested events'} on one line, where
    a loop's operation spans the operations of its body."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    self_ns, stack = {}, []
    for i in order:
        s, e = events[i][:2]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        self_ns[i] = e - s
        if stack:
            self_ns[stack[-1]] -= e - s
        stack.append(i)
    return self_ns


def host_spans(data) -> List[tuple]:
    """(start_ns, end_ns, name) of every `bench.` span on the host."""
    spans = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.end_ns, ev.name[len(SPAN_PREFIX):]))
    return sorted(spans)


def reduce_trace(path: str, kernels: Dict[str, str],
                 paths: Dict[str, str] = None) -> dict:
    """Reduce the `.xplane.pb` file at ``path``, or its gzip.

    -> {"window_s", "spans": {step: s}, "devices": [{"name", "busy_s",
    "kernel_s": {wrapper: s}, "ops": {name: own s}, "gaps": [(s, step)]}]}
    An operation is named by its kernel's wrapper, or by its instruction
    and, where ``paths`` has it, the tail of its `op_name`.
    """
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    spans = host_spans(data)
    if not spans:
        raise RuntimeError("the trace holds no bench. host span")
    w0, w1 = spans[0][0], max(e for _, e, _ in spans)
    span_s = collections.Counter()
    for s, e, name in spans:
        span_s[name] += (e - s) * 1e-9

    def step_at(t):
        inside = [(e - s, name) for s, e, name in spans if s <= t < e]
        return min(inside)[1] if inside else "outside any step"

    devices = []
    for plane in device_planes(data):
        events = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e > s:
                    name = op_name(ev.name)
                    label = kernels.get(name) or (
                        f"{name} {paths[name]}" if name in (paths or {}) else name)
                    events.append((s, e, label, name in kernels))
        kernel_s, ops = collections.Counter(), collections.Counter()
        for i, ns in _self_times(events).items():
            ops[events[i][2]] += ns * 1e-9
            if events[i][3]:
                kernel_s[events[i][2]] += (events[i][1] - events[i][0]) * 1e-9
        busy = _union([ev[:2] for ev in events])
        gaps, prev = [], w0
        for s, e in busy + [(w1, w1)]:
            if s > prev:
                gaps.append(((s - prev) * 1e-9, step_at(prev)))
            prev = max(prev, e)
        devices.append({
            "name": plane.name,
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "kernel_s": dict(kernel_s),
            "ops": dict(ops),
            "gaps": sorted(gaps, reverse=True),
        })
    if not devices:
        raise RuntimeError("the trace holds no device plane")
    return {"window_s": (w1 - w0) * 1e-9, "devices": devices, "spans": dict(span_s)}


def breakdown(red: dict) -> dict:
    """The contract's `breakdown`: the device operations that took most
    time, summed over the traced devices, and the longest single idle
    gaps, each named by the host step it began in."""
    ops = collections.Counter()
    for d in red["devices"]:
        ops.update(d["ops"])
    gaps = sorted(((s, f"{d['name']} idle while host in {step}")
                   for d in red["devices"] for s, step in d["gaps"]), reverse=True)
    return {
        "device_ops": [[k, v] for k, v in ops.most_common(TOP)],
        "idle_gaps": [[name, s] for s, name in gaps[:TOP]],
    }
