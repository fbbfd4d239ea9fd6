"""Reduce a profiler trace (``jax.profiler`` xplane) to the numbers the
per-layer metrics read: device busy time over the traced window, the
device time of each operation and kernel, and the idle gaps labelled by
the benchmark annotation (``bench.*``) the host was in.

``load`` reads the trace file into plain arrays; ``reduce`` works on
those arrays alone, so a test can hand it a constructed trace.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List

import numpy as np

WINDOW = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
_OP_LINES = ("XLA Ops",)
# stats that may carry a kernel's own name beside the op's HLO name
_NAME_STATS = ("long_name", "hlo_op", "tf_op", "name", "kernel_details")


@dataclass
class Trace:
    """Device operations per device and host annotations, in ns."""
    # per device: arrays "name", "label", "start", "end"
    devices: List[dict] = field(default_factory=list)
    # host annotations: arrays "name", "start", "end"
    host: Dict[str, np.ndarray] = field(default_factory=dict)


def _op_events(plane):
    lines = {ln.name: ln for ln in plane.lines}
    line = next((lines[n] for n in _OP_LINES if n in lines), None)
    if line is None:
        return None
    names, labels, starts, ends = [], [], [], []
    for ev in line.events:
        stats = dict(ev.stats)
        extra = " ".join(str(stats[s]) for s in _NAME_STATS if s in stats)
        names.append(ev.name)
        labels.append(f"{ev.name} {extra}")
        starts.append(ev.start_ns)
        ends.append(ev.start_ns + ev.duration_ns)
    return {"name": np.array(names, object),
            "label": np.array(labels, object),
            "start": np.array(starts, np.float64),
            "end": np.array(ends, np.float64)}


def load(trace_dir) -> Trace:
    """Read the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no xplane trace under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    tr = Trace()
    names, starts, ends = [], [], []
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops = _op_events(plane)
            if ops is not None and len(ops["start"]):
                tr.devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        names.append(ev.name)
                        starts.append(ev.start_ns)
                        ends.append(ev.start_ns + ev.duration_ns)
    tr.host = {"name": np.array(names, object),
               "start": np.array(starts, np.float64),
               "end": np.array(ends, np.float64)}
    return tr


def _union(starts, ends):
    """Merged busy intervals of possibly overlapping [start, end)."""
    if not len(starts):
        return np.zeros(0), np.zeros(0)
    o = np.argsort(starts, kind="stable")
    s, e = starts[o], np.maximum.accumulate(ends[o])
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > e[:-1]
    idx = np.nonzero(new)[0]
    return s[idx], np.append(e[idx[1:] - 1], e[-1])


def _label_at(host, t):
    """The innermost bench annotation (other than the window) that holds
    each time in ``t``, or "none"."""
    keep = host["name"] != WINDOW
    names, s, e = host["name"][keep], host["start"][keep], host["end"][keep]
    out = np.full(len(t), "none", object)
    if not len(s):
        return out
    o = np.argsort(s, kind="stable")
    names, s, e = names[o], s[o], e[o]
    i = np.searchsorted(s, t, side="right") - 1
    ok = (i >= 0) & (e[np.maximum(i, 0)] >= t)
    out[ok] = names[i[ok]]
    return out


def reduce(tr: Trace) -> dict:
    """Numbers over the traced window (the ``bench.window`` annotation,
    else the span of the device operations): ``window_s``, ``busy_s``
    (union of operation intervals, averaged over the devices that ran
    any), ``ops`` {name: s}, ``labels`` {name: name and stats}, ``gaps``
    [(label, s)] longest first and ``idle_by_label`` {label: s}. With
    no device operation in the window, ``busy_s`` is 0 and the maps are
    empty."""
    win = tr.host["name"] == WINDOW if len(tr.host.get("name", [])) else []
    if np.any(win):
        w0 = float(tr.host["start"][win][0])
        w1 = float(tr.host["end"][win][0])
    else:
        w0 = min((d["start"].min() for d in tr.devices), default=0.0)
        w1 = max((d["end"].max() for d in tr.devices), default=0.0)
    res = {"window_s": (w1 - w0) * 1e-9, "busy_s": 0.0, "ops": {},
           "labels": {}, "gaps": [], "idle_by_label": {}}
    busy, gaps_s, gaps_mid = [], [], []
    ops: Dict[str, float] = {}
    labels: Dict[str, str] = {}
    for d in tr.devices:
        s = np.clip(d["start"], w0, w1)
        e = np.clip(d["end"], w0, w1)
        m = e > s
        if not m.any():
            continue
        s, e = s[m], e[m]
        dur = (e - s) * 1e-9
        for name, lab, t in zip(d["name"][m], d["label"][m], dur):
            ops[name] = ops.get(name, 0.0) + float(t)
            labels[name] = lab
        us, ue = _union(s, e)
        busy.append(float((ue - us).sum()) * 1e-9)
        gs = np.concatenate([[w0], ue])
        ge = np.concatenate([us, [w1]])
        g = ge > gs
        gaps_s.append((ge - gs)[g] * 1e-9)
        gaps_mid.append(((gs + ge) / 2)[g])
    if not busy:
        return res
    res["busy_s"] = float(np.mean(busy))
    res["ops"] = dict(sorted(ops.items(), key=lambda kv: -kv[1]))
    res["labels"] = labels
    g_s = np.concatenate(gaps_s)
    labels = _label_at(tr.host, np.concatenate(gaps_mid)) \
        if len(tr.host.get("name", [])) else np.full(len(g_s), "none",
                                                     object)
    order = np.argsort(-g_s, kind="stable")
    res["gaps"] = [(str(labels[i]), float(g_s[i])) for i in order]
    idle: Dict[str, float] = {}
    for lab, t in zip(labels, g_s):
        idle[str(lab)] = idle.get(str(lab), 0.0) + float(t)
    res["idle_by_label"] = dict(sorted(idle.items(), key=lambda kv: -kv[1]))
    return res


def kernel_seconds(res: dict, kernel: str) -> float:
    """Summed device time of the Pallas kernel called through the jitted
    wrapper ``kernel`` (``repro.kernels.ops``): XLA names the custom
    call after the wrapper (``%fused_expand.41 = ...``, with suffixes
    such as ``.clone`` where a pass copies it), and its op_name ends in
    ``jit(fused_expand)/pallas_call``. An operation that only takes the
    kernel's output as an operand does not count."""
    head = re.compile(r"^%?" + re.escape(kernel) + r"(\.[\w.]+)?(\s|=|$)")
    scope = f"jit({kernel})/pallas_call"
    return sum(t for name, t in res["ops"].items()
               if head.match(name) or scope in res["labels"][name])
