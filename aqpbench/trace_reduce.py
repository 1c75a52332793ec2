"""From a profiler trace to the benchmark's device numbers.

Two steps, kept apart so the second can be checked on a small recorded
trace (``aqpbench/tests/trace_sample.json``) without a chip:

1. :func:`extract` reads a JAX profiler ``.xplane.pb`` and keeps the
   benchmark's own host spans (names starting with ``SPAN_PREFIX``) and the
   events of every device plane's op line.
2. :func:`reduce` computes, inside the traced window (the extent of the host
   spans), the busy time of each device (the union of its op intervals),
   the time of the ESTIMATE kernels, the ops that took most self time (an
   op's time less that of the ops nested in it), and the idle gaps, cut
   where a host span starts or ends and labelled with the span in progress.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "aqpbench."
# Device-plane line that holds one event per executed op.
OP_LINES = ("XLA Ops",)
# Substrings of the ESTIMATE kernels' op names (the Pallas calls of
# kernels/poisson_bootstrap and kernels/segment_agg).
ESTIMATE_KERNELS = ("poisson_bootstrap", "segment_boot", "bootstrap_moments",
                    "_boot_kernel")


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def extract(xplane_path: str) -> Dict:
    """``{"host": [[label, start_ns, end_ns], ...],
    "device": {plane: [[op, start_ns, end_ns], ...]}}``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    host: List[List] = []
    device: Dict[str, List[List]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = [ln for ln in plane.lines if ln.name in OP_LINES]
            evs = device.setdefault(plane.name, [])
            for ln in lines:
                for e in ln.events:
                    evs.append([op_name(e.name), float(e.start_ns),
                                float(e.end_ns)])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append([e.name[len(SPAN_PREFIX):],
                                     float(e.start_ns), float(e.end_ns)])
    return {"host": host, "device": {k: v for k, v in device.items() if v}}


def op_name(hlo: str) -> str:
    """``%poisson_bootstrap_moments_lanes.9 = f32[...] custom-call(...)``
    -> ``poisson_bootstrap_moments_lanes``: the instruction's name without
    its numeric suffix, so that events of one op add up."""
    name = hlo.split(" = ", 1)[0].strip().lstrip("%")
    base, _, suffix = name.rpartition(".")
    return base if base and suffix.isdigit() else name


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _label_at(t: float, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The innermost (latest started) host span covering ``t``."""
    best: Optional[Tuple[float, str]] = None
    for name, a, b in spans:
        if a <= t <= b and (best is None or a > best[0]):
            best = (a, name)
    return best[1] if best else "other"


def reduce(ex: Dict, top: int = 10) -> Optional[Dict]:
    """Device numbers of the traced window, or None if nothing ran there."""
    spans = [(n, a, b) for n, a, b in ex["host"]]
    if not spans or not ex["device"]:
        return None
    w0 = min(a for _, a, _ in spans)
    w1 = max(b for _, _, b in spans)
    window_s = (w1 - w0) * 1e-9
    busy, kernel_s, gaps = [], 0.0, []
    ops: Dict[str, float] = {}
    for plane, evs in sorted(ex["device"].items()):
        clipped = sorted(((name, max(a, w0), min(b, w1)) for name, a, b in evs
                          if b > w0 and a < w1), key=lambda e: (e[1], -e[2]))
        # Self time: an op that encloses others (a while loop, a branch)
        # keeps only the time none of its children covers.
        self_ns = [b - a for _, a, b in clipped]
        stack: List[int] = []
        for i, (name, a, b) in enumerate(clipped):
            while stack and clipped[stack[-1]][2] <= a:
                stack.pop()
            if stack and b <= clipped[stack[-1]][2]:
                self_ns[stack[-1]] -= b - a
            stack.append(i)
        for (name, a, b), t in zip(clipped, self_ns):
            ops[name] = ops.get(name, 0.0) + max(t, 0.0) * 1e-9
            if any(k in name for k in ESTIMATE_KERNELS):
                kernel_s += (b - a) * 1e-9
        u = _union([(a, b) for _, a, b in clipped])
        busy.append(sum(b - a for a, b in u) * 1e-9)
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            # Split a gap where a host span starts or ends inside it.
            cuts = sorted({a, b} | {t for _, s0, s1 in spans
                                    for t in (s0, s1) if a < t < b})
            for c0, c1 in zip(cuts, cuts[1:]):
                gaps.append((_label_at((c0 + c1) / 2, spans),
                             (c1 - c0) * 1e-9))
    n_dev = len(busy)
    if sum(busy) <= 0:
        return None
    idle_by_label: Dict[str, float] = {}
    for label, s in gaps:
        idle_by_label[label] = idle_by_label.get(label, 0.0) + s / n_dev
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n_dev,
        "estimate_kernel_s": kernel_s / n_dev,
        "idle_by_label": idle_by_label,
        "device_ops": sorted(([k, v / n_dev] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in gaps),
                            key=lambda kv: -kv[1])[:top],
    }
