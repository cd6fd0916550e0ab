"""Device time by the program's own scope names, and the registry's clock
placed on the profiler's.

The step programs name their layers with ``jax.named_scope`` (the
vocabulary below).  The scope path reaches each device op of a profiler
trace as its HLO ``op_name``, which a TPU trace keeps in the ``tf_op``
stat of the op's event metadata, not of the event itself, so
``ProfileData`` does not show it: ``_event_metadata_ops`` reads it from
the ``.xplane.pb`` with a minimal schema of ``xplane.proto``.  ``load``
reads the traced window's file (the one ``trace_reduce.find_xplane``
finds) into the plain form of ``trace_reduce.load`` with that op_name as
a fourth field per op; ``of_run`` does so once per traced run and keeps
the result on the run for all its readers.

``program_tables`` charges the device time of every op (control-flow ops
left out, as ``trace_reduce`` does) inside each ``prefill_step`` /
``decode_step`` module run that lies wholly inside the window to the
innermost vocabulary scope of its op_name, and gives per program the mean
ms per run of each scope and the share of op time that has none.  A fused
op is one event, charged whole by the op_name XLA gives the fusion, its
root's.  The Pallas kernels carry no op_name; they are known by the names
their ``pallas_call``s give them.

``clock_offset_ns`` places the registry's spans (``perf_counter`` seconds
from ``Registry.t0``) on the trace's clock (ns from its start): every
``engine/*_step`` span is on both, and the median offset of the matched
pairs maps a registry-only span, such as a request's, onto the trace.

Every function here returns ``None`` or an empty result when there is
nothing to read; none raises on a trace without TPU planes or scopes.
"""
from __future__ import annotations

import bisect
import statistics
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import trace_reduce

ROOT = Path(__file__).resolve().parent.parent
TRACE_DIR = ROOT / ".bench_out" / "trace"

# the step programs' scope vocabulary, each with its layer (BENCHMARK.json)
LAYER_OF = {
    "pool_gather": "serving/pool.py",
    "pool_scatter": "serving/pool.py",
    "plan_scores": "core/plan.py and core/quoka.py",
    "plan_topk": "core/plan.py and core/quoka.py",
    "plan_materialize": "core/plan.py and core/quoka.py",
    "ops_attention": "kernels/",
    "ops_selected_attention": "kernels/",
    "ops_score": "kernels/",
    "embed": "models/",
    "attn_proj": "models/",
    "kv_write": "models/",
    "mlp": "models/",
    "readout": "models/",
    "stack": "models/",
    "sample": "serving/engine.py",
}
# scopes named with the kernel backend as a suffix (``ops_score_pallas``)
_SUFFIXED = ("ops_attention", "ops_selected_attention", "ops_score")
# the Pallas kernels by their pallas_call names, with the scope path their
# call site has
KERNEL_PATH = {
    "flash_attention_bhtd": "ops_attention",
    "quoka_score_bhtd": "plan_scores/ops_score",
    "selected_attention_bhtd": "ops_selected_attention",
    "selected_attention_paged": "ops_selected_attention",
}
# the scan's own ops under ``stack``: each layer's weights sliced out by the
# scan (``dynamic_slice``, with the copies XLA adds for their layout), the
# cache views indexed by layer (``dynamic_slice`` then ``squeeze``; XLA
# names the fusion by its root), and the per-layer outputs stacked back
STACK_OPS = ("dynamic_slice", "squeeze", "dynamic_update_slice")
PROGRAMS = ("prefill_step", "decode_step")
NONE = "(no scope)"


def scope_path(op_name: str) -> Tuple[str, ...]:
    """The vocabulary scopes in an HLO op_name, outermost first.

    ``stack`` wraps the whole layer scan, so it is innermost for anything
    in a layer that has no scope of its own; it is kept only on the scan's
    slices (an op_name ending in one of ``STACK_OPS``), and any other op
    whose innermost scope it is counts as having none."""
    comps = op_name.split("/")
    out = []
    for comp in comps:
        if comp in LAYER_OF:
            out.append(comp)
            continue
        for base in _SUFFIXED:
            if comp.startswith(base + "_") and \
                    comp[len(base) + 1:].isidentifier():
                out.append(base)
                break
    if out and out[-1] == "stack" and comps[-1] not in STACK_OPS:
        out.pop()
    return tuple(out)


def op_scope(name: str, op_name: str) -> Tuple[str, ...]:
    """An op's scope path: from its op_name, else from its kernel name."""
    path = scope_path(op_name) if op_name else ()
    if not path:
        kernel = KERNEL_PATH.get(trace_reduce._strip(name))
        if kernel:
            path = tuple(kernel.split("/"))
    return path


def _xspace_class():
    """A message class for the parts of ``xplane.proto``'s ``XSpace`` that
    carry event metadata: planes, their event and stat metadata (the maps
    read as their wire form, repeated key/value entries)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                             package="bench_xplane")

    def message(name, *fields):
        m = fdp.message_type.add(name=name)
        for fname, num, typ, label, tname in fields:
            f = m.field.add(name=fname, number=num, type=typ, label=label)
            if tname:
                f.type_name = ".bench_xplane." + tname

    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, msg = F.TYPE_INT64, F.TYPE_MESSAGE
    message("XStat", ("metadata_id", 1, i64, one, None),
            ("str_value", 5, F.TYPE_STRING, one, None),
            ("ref_value", 7, F.TYPE_UINT64, one, None))
    message("XEventMetadata", ("id", 1, i64, one, None),
            ("name", 2, F.TYPE_STRING, one, None),
            ("stats", 5, msg, many, "XStat"))
    message("XStatMetadata", ("id", 1, i64, one, None),
            ("name", 2, F.TYPE_STRING, one, None))
    message("EventEntry", ("key", 1, i64, one, None),
            ("value", 2, msg, one, "XEventMetadata"))
    message("StatEntry", ("key", 1, i64, one, None),
            ("value", 2, msg, one, "XStatMetadata"))
    message("XPlane", ("name", 2, F.TYPE_STRING, one, None),
            ("event_metadata", 4, msg, many, "EventEntry"),
            ("stat_metadata", 5, msg, many, "StatEntry"))
    message("XSpace", ("planes", 1, msg, many, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _event_metadata_ops(path: str) -> Dict[str, Dict[str, str]]:
    """Per TPU plane, each event name's ``tf_op`` (its HLO op_name) from
    the event metadata of the file; empty where the file has none."""
    try:
        from google.protobuf.message import DecodeError
    except ImportError as e:
        print(f"bench/scopes.py: no op_names read: {e!r}", file=sys.stderr)
        return {}
    try:
        with open(path, "rb") as f:
            space = _xspace_class().FromString(f.read())
    except (OSError, DecodeError) as e:
        print(f"bench/scopes.py: no op_names read: {e!r}", file=sys.stderr)
        return {}
    out = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op = [k for k, n in names.items() if n == "tf_op"]
        ops = {}
        for e in plane.event_metadata:
            for st in e.value.stats:
                if st.metadata_id in tf_op:
                    v = st.str_value or names.get(st.ref_value, "")
                    ops[e.value.name] = v.rstrip(":")
                    break
        out[plane.name] = ops
    return out


def load(path: str) -> Dict:
    """The plain form of ``trace_reduce.load`` with each device op's HLO
    op_name (``""`` where it has none) as a fourth field."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    meta = _event_metadata_ops(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            ops, mods = [], []
            names = meta.get(plane.name, {})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [[e.name, e.start_ns, e.duration_ns,
                             names.get(e.name, "")] for e in line.events]
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
                         if e.name.startswith(trace_reduce.HOST_PREFIXES)]
    return {"devices": devices, "host": host}


def window(trace: Dict) -> Tuple[float, float]:
    """``trace_reduce.window_of``, for the plain form with op_names."""
    if trace["host"]:
        return trace_reduce.window_of(trace)
    pts = [(s, s + d) for dev in trace["devices"]
           for _, s, d, _ in dev["ops"]]
    return min(a for a, _ in pts), max(b for _, b in pts)


def program_tables(trace: Dict, win=None) -> Dict:
    """Per step program: its module runs in the window, the mean device ms
    per run of each innermost scope, the ms per run of each scope path,
    the share of op time with no scope, and the unscoped ops that took
    most time (by the tail of their op_name, else by op)."""
    if not any(dev["ops"] for dev in trace.get("devices", ())):
        return {}
    lo, hi = win if win is not None else window(trace)
    acc: Dict[str, Dict] = {}
    for dev in trace["devices"]:
        ops = sorted((s, d, n, on) for n, s, d, on in dev["ops"]
                     if trace_reduce._strip(n)
                     not in trace_reduce.CONTROL_FLOW)
        starts = [s for s, *_ in ops]
        for mname, ms, md in dev["modules"]:
            prog = next((p for p in PROGRAMS if p in mname), None)
            if prog is None or ms < lo or ms + md > hi:
                continue
            a = acc.setdefault(prog, {"runs": 0, "module_ns": 0.0,
                                      "op_ns": 0.0, "inner": {}, "paths": {},
                                      "none": {}})
            a["runs"] += 1
            a["module_ns"] += md
            i = bisect.bisect_left(starts, ms)
            while i < len(ops) and ops[i][0] < ms + md:
                s, d, n, on = ops[i]
                i += 1
                d = min(s + d, ms + md) - s
                path = op_scope(n, on)
                a["op_ns"] += d
                inner = path[-1] if path else NONE
                a["inner"][inner] = a["inner"].get(inner, 0.0) + d
                a["paths"][path] = a["paths"].get(path, 0.0) + d
                if not path:
                    k = ("/".join(on.split("/")[-3:]) if on
                         else trace_reduce._strip(n))
                    a["none"][k] = a["none"].get(k, 0.0) + d
    out = {}
    for prog, a in acc.items():
        r = a["runs"]
        out[prog] = {
            "runs": r,
            "module_ms": a["module_ns"] / r * 1e-6,
            "op_ms": a["op_ns"] / r * 1e-6,
            "ms": {k: v / r * 1e-6 for k, v in
                   sorted(a["inner"].items(), key=lambda kv: -kv[1])},
            "paths": {k: v / r * 1e-6 for k, v in a["paths"].items()},
            "unscoped_share": (a["inner"].get(NONE, 0.0) / a["op_ns"]
                               if a["op_ns"] else None),
            "unscoped_top": [[k, v / r * 1e-6] for k, v in sorted(
                a["none"].items(), key=lambda kv: -kv[1])[:8]],
        }
    return out


def ms_under(tables: Dict, program: str, scopes) -> Optional[float]:
    """Mean device ms per run of ``program`` in ops under any of
    ``scopes`` at any depth, each op counted once; None without runs of
    the program or without any op under them."""
    t = tables.get(program)
    if not t:
        return None
    got = [v for path, v in t["paths"].items()
           if any(sc in path for sc in scopes)]
    return sum(got) if got else None


# ---------------------------------------------------------------------------
# the registry's clock on the trace's
# ---------------------------------------------------------------------------

STEP_SPANS = ("engine/prefill_step", "engine/decode_step")


def clock_offset_ns(reg_events: List[Dict], host: List) -> Optional[float]:
    """ns to add to a registry event's start (``ts`` in us from
    ``Registry.t0``) to place it on the trace's clock: the median offset
    of the step spans matched between the two.  The trace holds a
    contiguous run of the registry's step spans (the profiler saw only
    part of the window), so the run is found by the shift whose offsets
    agree best."""
    reg = sorted((ev["ts"] * 1e3, ev["name"]) for ev in reg_events
                 if ev.get("name") in STEP_SPANS)
    tr = sorted((s, n) for n, s, _ in host if n in STEP_SPANS)
    if not reg or not tr or len(tr) > len(reg):
        return None
    best = None
    for shift in range(len(reg) - len(tr) + 1):
        if any(reg[shift + i][1] != n for i, (_, n) in enumerate(tr)):
            continue
        offs = [s - reg[shift + i][0] for i, (s, _) in enumerate(tr)]
        mid = statistics.median(offs)
        spread = max(abs(o - mid) for o in offs)
        if best is None or spread < best[0]:
            best = (spread, mid)
    return None if best is None else best[1]


def on_trace(reg_events: List[Dict], offset_ns: float,
             prefix: str) -> List:
    """The registry's spans named ``prefix...`` as ``[name, start_ns,
    dur_ns, args]`` on the trace's clock."""
    return [[ev["name"], ev["ts"] * 1e3 + offset_ns, ev["dur"] * 1e3,
             ev.get("args", {})]
            for ev in reg_events if ev["name"].startswith(prefix)]


def idle_by_request_state(trace: Dict, requests: List,
                          win=None) -> Dict[str, float]:
    """Device idle seconds of the window, averaged over devices, by what
    the requests were doing at each idle gap's midpoint: some request
    queued (due, not admitted), else some in prefill, else some decoding,
    else none in flight."""
    lo, hi = win if win is not None else window(trace)
    order = ("request/queued", "request/prefill", "request/decode")
    spans = {n: [(s, s + d) for nm, s, d, _ in requests if nm == n]
             for n in order}
    out: Dict[str, float] = {}
    n_dev = max(1, len(trace["devices"]))
    for dev in trace["devices"]:
        iv = trace_reduce._union(trace_reduce._clip(
            [(s, s + d) for _, s, d, *_ in dev["ops"]], lo, hi))
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            t = (a + b) / 2
            lab = next((n for n in order
                        if any(s <= t <= e for s, e in spans[n])),
                       "no request in flight")
            out[lab] = out.get(lab, 0.0) + (b - a) / n_dev * 1e-9
    return out


# ---------------------------------------------------------------------------
# one traced run
# ---------------------------------------------------------------------------

def of_run(run) -> Optional[Dict]:
    """The scope tables of a traced run (``bench/run.py``'s ``RunView``),
    computed once per run and kept on it; their summary goes into
    ``run.notes`` so the run's diagnostics carry it.  None when the run
    was not traced or its trace has no TPU ops."""
    got = getattr(run, "_scopes", False)
    if got is not False:
        return got
    run._scopes = None
    if getattr(run, "trace", None) is None:
        return None
    path = trace_reduce.find_xplane(str(TRACE_DIR))
    if path is None:
        return None
    trace = load(path)
    tables = program_tables(trace)
    if not tables:
        return None
    run._scopes = {"trace": trace, "tables": tables}
    run.notes["device_ms_by_scope"] = {
        prog: {"runs": t["runs"], "module_ms": round(t["module_ms"], 4),
               "ms": {k: round(v, 4) for k, v in t["ms"].items()},
               "unscoped_share": t["unscoped_share"],
               "unscoped_top": [[k, round(v, 4)]
                                for k, v in t["unscoped_top"]]}
        for prog, t in tables.items()}
    reg = getattr(run, "registry", None)
    if reg is not None:
        off = clock_offset_ns(reg.trace_events, trace["host"])
        if off is not None:
            req = on_trace(reg.trace_events, off, "request/")
            run.notes["idle_s_by_request_state"] = idle_by_request_state(
                trace, req)
    return run._scopes
