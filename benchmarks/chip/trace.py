"""Profiler trace -> events -> per-layer quantities.

`load` reads the `.xplane.pb` that `jax.profiler` writes, keeping:

- per device plane (`/device:TPU:<n>`), the events of its `XLA Ops` line
  (one per HLO op execution, named by the op's HLO text) and of its
  `XLA Modules` line (one per program execution, `jit_<fn>(<hash>)`);
- on the host, the benchmark's own `bench.*` spans and the `PjitFunction`
  dispatch events, used to line the host clock up with the device's.

`reduce` turns those events into what the per-layer readers use: device
busy time (the union of op intervals), kernel and program time by name,
collective time exposed and hidden behind compute, the ops that took most
time, and the longest idle gaps with the host span that covers each.

Control-flow ops (`while`, `conditional`, `call`) span the ops of their
bodies, which are listed as well, so they are left out of every sum.
"""
from __future__ import annotations

import bisect
import dataclasses
import gzip
import json
import re
from typing import Optional

_OP = re.compile(r"%([A-Za-z_][A-Za-z0-9_\-]*?)(\.\d+)* = ")
_MODULE = re.compile(r"([^(]+)")
CONTROL_FLOW = frozenset({"while", "conditional", "call"})
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)")


def op_base(name: str) -> str:
    """`%fusion.12 = ...` -> `fusion`; `%flash_attention.6 = ...` ->
    `flash_attention`."""
    m = _OP.match(name)
    return m.group(1) if m else name.split(" ", 1)[0]


def module_base(name: str) -> str:
    """`jit_serve_step(9864...)` -> `jit_serve_step`."""
    return _MODULE.match(name).group(1)


@dataclasses.dataclass
class Trace:
    """Events as (name, start_ns, end_ns); device ones keyed by chip."""
    ops: dict          # chip -> [(op base name, start, end, is_kernel)]
    modules: dict      # chip -> [(module base name, start, end)]
    spans: list        # [(bench span name, start, end)] on the host clock
    dispatches: list   # [(function name, start)] PjitFunction on the host

    @classmethod
    def read(cls, path) -> "Trace":
        """A trace kept as gzipped JSON of these four fields."""
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls(ops={int(k): [tuple(e) for e in v]
                        for k, v in d["ops"].items()},
                   modules={int(k): [tuple(e) for e in v]
                            for k, v in d["modules"].items()},
                   spans=[tuple(e) for e in d["spans"]],
                   dispatches=[tuple(e) for e in d["dispatches"]])


def load(path) -> Trace:
    """Read an `.xplane.pb` written by `jax.profiler`."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops, modules, spans, dispatches = {}, {}, [], []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[chip] = [
                        (op_base(e.name), e.start_ns,
                         e.start_ns + e.duration_ns,
                         'custom_call_target="tpu_custom_call"' in e.name)
                        for e in line.events]
                elif line.name == "XLA Modules":
                    modules[chip] = [(module_base(e.name), e.start_ns,
                                      e.start_ns + e.duration_ns)
                                     for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.name.startswith("PjitFunction("):
                        fn = e.name[len("PjitFunction("):-1]
                        if fn.startswith("jit("):      # compiled ahead
                            fn = fn[4:-1]
                        dispatches.append((fn, e.start_ns))
    return Trace(ops, modules, sorted(spans, key=lambda s: (s[1], s[0])),
                 sorted(set(dispatches), key=lambda s: (s[1], s[0])))


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(merged, lo, hi) -> float:
    """Length of [lo, hi] covered by disjoint sorted intervals."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def host_skew(tr: Trace, chip: int) -> float:
    """Device clock minus host clock, in ns.

    A program cannot start on the device before the host dispatched it, and
    on an idle device it starts within microseconds; so the smallest
    (device start - host dispatch) over the k-th execution and the k-th
    dispatch of each program is the offset between the two clocks."""
    by_fn: dict = {}
    for fn, t in tr.dispatches:
        by_fn.setdefault("jit_" + fn, []).append(t)
    runs: dict = {}
    for name, s, _ in tr.modules.get(chip, []):
        runs.setdefault(name, []).append(s)
    diffs = [d - h for name, ds in runs.items()
             for d, h in zip(ds, _first_of_nested(by_fn.get(name, [])))]
    return min(diffs) if diffs else 0.0


def _first_of_nested(times):
    """JAX writes each dispatch as two nested events a few microseconds
    apart; keep the outer one."""
    out = []
    for t in times:
        if not out or t - out[-1] > 20_000:
            out.append(t)
    return out


@dataclasses.dataclass
class Reduced:
    """Per-layer quantities of one trace, in seconds."""
    window_s: float
    busy_s: float                  # mean over chips
    kernel_s: dict                 # kernel name -> (seconds, calls), chip 0
    module_s: dict                 # program name -> (seconds, runs), chip 0
    collective_s: Optional[float]  # mean over chips; None without any
    exposed_collective_s: Optional[float]
    top_ops: list                  # [[program/op, seconds]], chip 0
    idle_gaps: list                # [[host span, seconds]], chip 0


def reduce(tr: Trace, top=10) -> Reduced:
    """Reduce a trace over its window: from the first `bench.*` span's
    start to the last one's end, moved onto the device clock, and widened
    to the first and last device op where these lie outside it."""
    chips = sorted(tr.ops)
    if not chips:
        raise ValueError("the trace holds no device ops")
    all_ops = [o for c in chips for o in tr.ops[c]]
    lo = min(o[1] for o in all_ops)
    hi = max(o[2] for o in all_ops)
    skew = host_skew(tr, chips[0])
    if tr.spans:
        lo = min(lo, tr.spans[0][1] + skew)
        hi = max(hi, max(s[2] for s in tr.spans) + skew)
    busy, coll, exposed = [], [], []
    for c in chips:
        work = [o for o in tr.ops[c] if o[0] not in CONTROL_FLOW]
        compute = union((s, e) for n, s, e, _ in work
                        if not COLLECTIVE.match(n))
        colls = union((s, e) for n, s, e, _ in work if COLLECTIVE.match(n))
        busy.append(covered(union((s, e) for _, s, e, _ in work), lo, hi))
        if colls:
            total = covered(colls, lo, hi)
            hidden = sum(covered(compute, max(s, lo), min(e, hi))
                         for s, e in colls if e > lo and s < hi)
            coll.append(total)
            exposed.append(total - hidden)
    c0 = chips[0]
    ops0 = [o for o in tr.ops[c0] if o[0] not in CONTROL_FLOW
            and o[2] > lo and o[1] < hi]
    kernels: dict = {}
    for n, s, e, k in ops0:
        if k:
            t, cnt = kernels.get(n, (0.0, 0))
            kernels[n] = (t + (e - s) * 1e-9, cnt + 1)
    mods: dict = {}
    mod_iv = sorted((s, e, n) for n, s, e in tr.modules.get(c0, [])
                    if e > lo and s < hi)
    for s, e, n in mod_iv:
        t, cnt = mods.get(n, (0.0, 0))
        mods[n] = (t + (e - s) * 1e-9, cnt + 1)
    starts = [m[0] for m in mod_iv]
    by_op: dict = {}
    for n, s, e, _ in ops0:
        i = bisect.bisect_right(starts, s) - 1
        prog = mod_iv[i][2] if i >= 0 and s < mod_iv[i][1] else "?"
        key = f"{prog}/{n}"
        by_op[key] = by_op.get(key, 0.0) + (e - s) * 1e-9
    top_ops = sorted(([k, v] for k, v in by_op.items()),
                     key=lambda kv: (-kv[1], kv[0]))[:top]
    merged = union((s, e) for n, s, e, _ in ops0)
    gaps = []
    prev = lo
    for s, e in merged + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, min(s, hi)))
        prev = max(prev, e)
    named = []
    for s, e in gaps:
        mid = (s + e) / 2 - skew
        covering = [n for n, hs, he in tr.spans if hs <= mid <= he]
        named.append([covering[-1] if covering else "no bench span",
                      (e - s) * 1e-9])
    idle_gaps = sorted(named, key=lambda g: (-g[1], g[0]))[:top]
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / len(busy) * 1e-9,
        kernel_s=kernels, module_s=mods,
        collective_s=sum(coll) / len(chips) * 1e-9 if coll else None,
        exposed_collective_s=(sum(exposed) / len(chips) * 1e-9
                              if coll else None),
        top_ops=top_ops, idle_gaps=idle_gaps)
