"""From a profiler trace to the numbers the benchmark reports.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes (with
nothing but JAX's ``ProfileData``) into a small plain form: per device,
its operations and its XLA module runs, and the host's annotations (the
harness's ``bench/...`` spans and the program's ``engine/...`` spans), all
in nanoseconds on the trace's one clock.  The same form is what
``bench/testdata/`` keeps, so the reduction is checked on a recorded trace.

``reduce`` gives, over a window:

  * busy: the union of the intervals in which an operation ran, per
    device, averaged over the devices; idle share = 1 - busy / window;
  * the device operations that took most time (names with their numeric
    suffix dropped, so ``fusion.12`` and ``fusion.40`` add up);
  * idle time by what the host was doing: each idle gap is charged to the
    innermost host annotation open at its midpoint ("no host span" when
    none is);
  * per XLA module name, its runs and the time of its Pallas kernels.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

# the program's Pallas kernels, by the names their custom calls carry
KERNEL_PAT = re.compile(r"^(flash_attention_bhtd|quoka_score_bhtd)$")
# ops that contain other ops of the same line: left out of the op totals
CONTROL_FLOW = ("while", "conditional", "call")
HOST_PREFIXES = ("bench/", "engine/", "pool/")


def _strip(name: str) -> str:
    """An op's or module's short name: the HLO instruction name of an
    ``XLA Ops`` event (its text runs ``%name.12 = type op(...)``), with
    the ``%`` and numeric suffixes dropped; a module's name without its
    ``(fingerprint)``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"(\.(\d+|clone))+$", "", name)


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


def load(path: str) -> Dict:
    """The plain form of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [[e.name, e.start_ns, e.duration_ns]
                            for e in line.events]
                elif line.name == "XLA Modules":
                    mods += [[e.name, e.start_ns, e.duration_ns]
                             for e in line.events]
            if ops or mods:
                devices.append({"name": plane.name, "ops": ops,
                                "modules": mods})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events
                         if e.name.startswith(HOST_PREFIXES)]
    return {"devices": devices, "host": host}


def describe(path: str, n: int = 8) -> Dict:
    """Planes, lines and a few event names of a trace: what to look at
    once by hand before trusting the reduction."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = {"events": len(evs),
                                "names": sorted({e.name for e in evs})[:n]}
        out[plane.name] = lines
    return out


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


CLOSE = "bench/window_close"


def window_of(trace: Dict) -> Tuple[float, float]:
    """The span of the host's annotations (else of the device ops), up to
    where the harness marked the window's close (``bench/window_close``),
    when it did."""
    pts = [(s, s + d) for _, s, d in trace["host"]]
    if not pts:
        pts = [(s, s + d) for dev in trace["devices"] for _, s, d in dev["ops"]]
    close = [s for name, s, _ in trace["host"] if name == CLOSE]
    return min(a for a, _ in pts), min(close) if close \
        else max(b for _, b in pts)


def _host_label(host, t: float) -> str:
    best = None
    for name, s, d in host:
        if s <= t <= s + d and (best is None or s >= best[1]):
            best = (name, s)
    return re.sub(r"#\d+$", "", best[0]) if best else OUTSIDE


# idle time with no harness or engine span open: between the engine's
# step spans, in the serve loop's scheduling and bookkeeping
OUTSIDE = "between step spans"


def reduce(trace: Dict, window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> Dict:
    lo, hi = window if window is not None else window_of(trace)
    span = hi - lo
    busy, op_time, gaps = [], {}, {}
    modules: Dict[str, Dict] = {}
    for dev in trace["devices"]:
        iv = _union(_clip([(s, s + d) for _, s, d in dev["ops"]], lo, hi))
        busy.append(sum(b - a for a, b in iv))
        for name, s, d in dev["ops"]:
            a, b = max(s, lo), min(s + d, hi)
            k = _strip(name)
            if b > a and k not in CONTROL_FLOW:
                op_time[k] = op_time.get(k, 0.0) + (b - a)
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                lab = _host_label(trace["host"], (a + b) / 2)
                gaps[lab] = gaps.get(lab, 0.0) + (b - a)
        kops = sorted((s, d) for name, s, d in dev["ops"]
                      if KERNEL_PAT.match(_strip(name)))
        kstart = [s for s, _ in kops]
        for mname, ms, md in dev["modules"]:
            if ms < lo or ms + md > hi:
                continue
            m = modules.setdefault(_strip(mname), {"runs": [], "kernel_ns": []})
            i = bisect.bisect_left(kstart, ms)
            kern = 0
            while i < len(kops) and kops[i][0] < ms + md:
                kern += kops[i][1]
                i += 1
            m["runs"].append([ms, md])
            m["kernel_ns"].append(kern)
    n = max(1, len(trace["devices"]))
    busy_ns = sum(busy) / n
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": span * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "idle_pct": 100.0 * (1.0 - busy_ns / span) if span > 0 else None,
        "device_ops": [[k, v / n * 1e-9] for k, v in ops],
        "idle_gaps": [[k, v / n * 1e-9] for k, v in idle],
        "modules": modules,
    }


def slice_trace(trace: Dict, lo: float, hi: float) -> Dict:
    """The events of ``trace`` that start in [lo, hi), times from lo: the
    form ``bench/testdata`` keeps."""
    def cut(evs):
        return [[n, s - lo, d] for n, s, d in evs if lo <= s < hi]
    return {"devices": [{"name": d["name"], "ops": cut(d["ops"]),
                         "modules": cut(d["modules"])}
                        for d in trace["devices"]],
            "host": cut(trace["host"])}

