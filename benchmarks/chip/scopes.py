"""Profiler trace -> device time by the program's own scopes, and what the
host threads were doing in each idle gap.

`trace.py` knows device work by the names the compiler picked (an op's HLO
base name, a program's jit name) and the host only by the benchmark's
`bench.*` spans.  The program names its work with `jax.named_scope`
(`models/model.py`): each device op of the trace carries that scope path
in its HLO `op_name`.  `load` reads the `.xplane.pb` once more and keeps,
beside what `trace.load` keeps:

- each device op's scope path: the named-scope components of its HLO
  `op_name` (the xplane's `tf_op` stat), less the `jit(...)` wrappers,
  einsum specs and control flow that jax adds, and less the primitive; ""
  where the op has none (XLA gives a layout `copy` none, for one);
- every host event with its thread (the xplane line it lies on): the
  benchmark's spans and the Python threads' own events (a Python thread is
  one that holds either), and the runtime's threads' events; the main
  thread is the Python thread that dispatched the programs.

`reduce` adds two quantities to `trace.reduce`'s, over the same window:

- `scope_s`: per program, device seconds by scope path (chip 0), with
  `(unscoped)` for ops that carry no scope;
- `idle_gap_hosts`: for each of the longest idle gaps, the `bench.*` span
  `trace.reduce` names it by, the innermost event on the main thread and
  the innermost event on any of the runtime's threads at the gap's middle
  ("none" where there is none), and its length.
"""
from __future__ import annotations

import bisect
import dataclasses
import gzip
import importlib.util
import json
import pathlib
import re
import sys


def _bench_trace():
    """`trace.py`, as `run.py` loads it (by path: `trace` is also a module
    of the standard library)."""
    if "bench_trace" not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            "bench_trace", pathlib.Path(__file__).resolve().parent
            / "trace.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules["bench_trace"] = mod
        spec.loader.exec_module(mod)
    return sys.modules["bench_trace"]


_trace = _bench_trace()

UNSCOPED = "(unscoped)"
# name-stack components that jax itself adds around a scope: control flow
# (the layer scan's while loop and its body) and rematerialization
JAX_MARKS = frozenset({"while", "body", "cond", "closed_call", "checkpoint",
                       "rematted_computation"})
_IDENT = re.compile(r"[A-Za-z_]\w*")


def scope_path(op_name: str) -> str:
    """`jit(serve_step)/while/body/closed_call/attn/jit(decode_attention)/
    kv/jit(_pad)/pad` -> `attn/kv`.  Where ops were merged, their names
    are joined by `;`: the first is kept."""
    parts = op_name.split(";", 1)[0].split("/")[:-1]
    return "/".join(p for p in parts
                    if _IDENT.fullmatch(p) and p not in JAX_MARKS)


@dataclasses.dataclass
class Scoped:
    """A trace as `trace.load` keeps it, and what it leaves out."""
    trace: _trace.Trace
    ops: dict         # chip -> [(op base name, scope path, start, end)]
    host: list        # [(thread, event name, start, end)], host clock
    python: list      # the Python threads
    main: str         # the Python thread that dispatched the programs

    def save(self, path) -> None:
        """Gzipped JSON that `trace.Trace.read` reads too."""
        t = self.trace
        d = {"ops": t.ops, "modules": t.modules, "spans": t.spans,
             "dispatches": t.dispatches, "scoped_ops": self.ops,
             "host": self.host, "python": self.python, "main": self.main}
        with gzip.open(path, "wt") as f:
            json.dump(d, f)

    @classmethod
    def read(cls, path) -> "Scoped":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls(trace=_trace.Trace.read(path),
                   ops={int(k): [tuple(e) for e in v]
                        for k, v in d["scoped_ops"].items()},
                   host=[tuple(e) for e in d["host"]], python=d["python"],
                   main=d["main"])


def _varint(b, i):
    v = shift = 0
    while True:
        c = b[i]
        i += 1
        v |= (c & 0x7F) << shift
        if c < 0x80:
            return v, i
        shift += 7


def _fields(b):
    """(field number, value) of a serialized protobuf message: an int for
    varints, bytes for the rest."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif kind in (1, 5):           # fixed 64 or 32 bits
            size = 8 if kind == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, v


def _entry(b) -> tuple:
    """A protobuf map entry: (key, value)."""
    d = dict(_fields(b))
    return d.get(1), d.get(2, b"")


def tf_ops(path) -> dict:
    """Device plane name -> the `tf_op` stat (the HLO `op_name`) of each
    event of its `XLA Ops` line, in order.

    `ProfileData` does not show the stats of an event's metadata, where the
    xplane keeps `tf_op`, so this reads the `XSpace` proto
    (`tsl/profiler/protobuf/xplane.proto`) itself: XSpace.planes = 1;
    XPlane name = 2, lines = 3, event_metadata = 4, stat_metadata = 5;
    XLine name = 2, events = 4; XEvent metadata_id = 1; XEventMetadata
    stats = 5; XStat metadata_id = 1, str_value = 5, ref_value = 7;
    XStatMetadata name = 2."""
    out = {}
    for f, plane in _fields(memoryview(pathlib.Path(path).read_bytes())):
        fields = list(_fields(plane)) if f == 1 else []
        name = bytes(next((v for g, v in fields if g == 2), b"")).decode()
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for g, v in fields:
            if g == 5:
                k, meta = _entry(v)
                stat_names[k] = bytes(dict(_fields(meta)).get(2, b"")
                                      ).decode()
        tf_op = [k for k, n in stat_names.items() if n == "tf_op"]
        op_of = {}
        for g, v in fields:
            if g == 4:
                k, meta = _entry(v)
                for h, stat in _fields(meta):
                    st = dict(_fields(stat)) if h == 5 else {}
                    if tf_op and st.get(1) == tf_op[0]:
                        op_of[k] = (bytes(st[5]).decode() if 5 in st
                                    else stat_names.get(st.get(7), ""))
        for g, line in fields:
            lf = list(_fields(line)) if g == 3 else []
            if bytes(dict(lf).get(2, b"")) == b"XLA Ops":
                out[name] = [op_of.get(dict(_fields(ev)).get(1), "")
                             for h, ev in lf if h == 4]
    return out


def load(path) -> Scoped:
    """Read an `.xplane.pb` written by `jax.profiler`."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    names = tf_ops(path)
    ops, host, python = {}, [], set()
    dispatched: dict = {}
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs = list(line.events)
                    op_names = names.get(plane.name, [])
                    if len(op_names) != len(evs):
                        raise ValueError(f"{plane.name}: {len(evs)} ops, "
                                         f"{len(op_names)} op names")
                    ops[int(m.group(1))] = [
                        (_trace.op_base(e.name), scope_path(n),
                         e.start_ns, e.start_ns + e.duration_ns)
                        for e, n in zip(evs, op_names)]
        elif plane.name == "/host:CPU":
            for i, line in enumerate(plane.lines):
                thread = f"{line.name}#{i}"
                for e in line.events:
                    host.append((thread, e.name, e.start_ns,
                                 e.start_ns + e.duration_ns))
                    if e.name.startswith(("$", "bench.")):
                        python.add(thread)
                    if e.name.startswith("PjitFunction("):
                        dispatched[thread] = dispatched.get(thread, 0) + 1
    main = max((t for t in dispatched if t in python), default="",
               key=dispatched.get)
    return Scoped(_trace.load(path), ops,
                  sorted(host, key=lambda h: (h[2], h[0], h[1])),
                  sorted(python), main)


def window(tr: _trace.Trace) -> tuple:
    """(lo, hi, skew) in ns on the device clock, as `trace.reduce` takes
    them."""
    all_ops = [o for c in sorted(tr.ops) for o in tr.ops[c]]
    if not all_ops:
        raise ValueError("the trace holds no device ops")
    lo = min(o[1] for o in all_ops)
    hi = max(o[2] for o in all_ops)
    skew = _trace.host_skew(tr, min(tr.ops))
    if tr.spans:
        lo = min(lo, tr.spans[0][1] + skew)
        hi = max(hi, max(s[2] for s in tr.spans) + skew)
    return lo, hi, skew


@dataclasses.dataclass
class Reduced:
    """What `reduce` adds to `trace.reduce`'s quantities, in seconds."""
    scope_s: dict          # program -> {scope path: seconds}, chip 0
    idle_gap_hosts: list   # [[bench span, main thread, runtime, seconds]]

    def top(self, n=10) -> list:
        """[[program/scope, seconds]], the n that took most time."""
        rows = [[f"{p}/{s}", t] for p, by in self.scope_s.items()
                for s, t in by.items()]
        return sorted(rows, key=lambda r: (-r[1], r[0]))[:n]

    def ms_per_run(self, module_s: dict, program: str, scope: str):
        """Device time of the ops under `scope` (itself or any scope within
        it) per execution of `program`, in ms; `module_s` is
        `trace.reduce`'s.  None where the trace has no such op."""
        t = sum(v for k, v in self.scope_s.get(program, {}).items()
                if k == scope or k.startswith(scope + "/"))
        _, n = module_s.get(program, (0.0, 0))
        return 1e3 * t / n if t and n else None


def _innermost(events, t) -> str:
    """The latest-started of the events that cover time t, or "none"."""
    best = None
    for ev in events:
        if ev[2] <= t <= ev[3] and ev[3] > ev[2] and (
                best is None or ev[2] >= best[2]):
            best = ev
    return "none" if best is None else best[1]


def reduce(sc: Scoped, top=10) -> Reduced:
    """`scope_s` and the `top` longest gaps' hosts, over the window and with
    the gaps of `trace.reduce`."""
    tr = sc.trace
    lo, hi, skew = window(tr)
    c0 = min(tr.ops)
    ops0 = [o for o in sc.ops[c0] if o[0] not in _trace.CONTROL_FLOW
            and o[3] > lo and o[2] < hi]
    mod_iv = sorted((s, e, n) for n, s, e in tr.modules.get(c0, [])
                    if e > lo and s < hi)
    starts = [m[0] for m in mod_iv]
    scope_s: dict = {}
    for _, scope, s, e in ops0:
        i = bisect.bisect_right(starts, s) - 1
        prog = mod_iv[i][2] if i >= 0 and s < mod_iv[i][1] else "?"
        by = scope_s.setdefault(prog, {})
        key = scope or UNSCOPED
        by[key] = by.get(key, 0.0) + (e - s) * 1e-9
    # the gaps exactly as trace.reduce finds, names and orders them
    merged = _trace.union((s, e) for _, _, s, e in ops0)
    gaps, prev = [], lo
    for s, e in merged + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, min(s, hi)))
        prev = max(prev, e)
    named = []
    for s, e in gaps:
        mid = (s + e) / 2 - skew
        covering = [n for n, hs, he in tr.spans if hs <= mid <= he]
        named.append((covering[-1] if covering else "no bench span",
                      (e - s) * 1e-9, mid))
    named.sort(key=lambda g: (-g[1], g[0]))
    main = [h for h in sc.host if h[0] == sc.main]
    runtime = [h for h in sc.host if h[0] not in sc.python]
    rows = [[n, _innermost(main, mid), _innermost(runtime, mid), t]
            for n, t, mid in named[:top]]
    return Reduced(scope_s=scope_s, idle_gap_hosts=rows)

