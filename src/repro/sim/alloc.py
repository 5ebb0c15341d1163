"""Numeric cores for the engine hot loop: dict reference vs flat arrays.

`Engine.run` used to re-solve max-min water-filling over *all* flows x
resources in pure-Python dicts at every event, which capped studies at a
few dozen nodes.  This module factors the numeric state of the loop —
remaining work, rates, busy/delivered accounting, completion detection —
behind a small core interface with two implementations:

  * `DictCore`   — the original dict hot loop, verbatim.  Kept as the
                   bit-exact reference (``Engine(backend="legacy")``)
                   and as the baseline the perf CI lane measures
                   against.
  * `ArrayCore`  — the default (``backend="array"``).  The flow/resource
                   incidence is a CSR-style int-index structure over
                   stable resource ids, updated incrementally as tasks
                   start/stop; `vector_water_fill` /
                   `vector_progressive_fill` run the allocator's
                   bottleneck-freeze iteration as numpy array programs;
                   and the solve is **incremental**: start/stop events
                   dirty only the resources they touch, and the next
                   solve recomputes just the connected components of the
                   incidence graph that contain a dirty resource,
                   splicing cached rates for every untouched component.
                   Because dirt accrues between solves, N same-timestamp
                   completions (or submissions) cost one re-solve, not N.

Bit-compatibility with the dict reference is by construction, not by
tolerance: the vectorized allocators replay the exact reference
arithmetic — `np.subtract.at` applies the same per-hold sequential
subtractions the dict loop does (never a fused ``k*m``), tie groups use
exact float equality, and a per-component solve performs the identical
operation sequence the global solve would (rounds never mix
components' capacities).  Rates, progress updates, `min_dt` and
completion thresholds are therefore bitwise equal and event traces are
byte-identical across backends; only `delivered` (utilized-time)
accumulates in a different association order and may differ at the last
ulp.  `tests/test_sim_alloc.py` pins all of this.

Max-min water-filling decomposes over connected components of the
flow/resource graph: a round's global minimum fair share only ever pins
flows — and subtracts capacity — inside the component that attains it,
so solving a component in isolation performs the identical float
operation sequence the global solve would.  That is the invariant that
makes component-level caching sound *and* bit-exact.
"""
from __future__ import annotations

import math
import time
from typing import Callable, Dict

import numpy as np

_EPS = 1e-12                       # matches repro.sim.engine._EPS

BACKENDS = ("array", "legacy")

# Water-fill round-loop implementations selectable on the array core:
# "numpy" is `vector_water_fill`; "jit" routes large components through
# `vector_water_fill_jit` (jax.jit over the same CSR arrays, bitwise
# the same rates — see its docstring) and falls back to numpy for small
# ones (below `_JIT_MIN_FLOWS`, dispatch overhead beats the kernel).
# Mixing the two per component is safe
# precisely because the rates are bitwise equal.
SOLVERS = ("numpy", "jit")

_JIT_MIN_FLOWS = 192


# ---------------------------------------------------------------------------
# Vectorized allocators over a CSR flow -> resource incidence
# ---------------------------------------------------------------------------


def vector_progressive_fill(indptr: np.ndarray, indices: np.ndarray,
                            cap: np.ndarray,
                            holds: np.ndarray) -> np.ndarray:
    """`engine.progressive_fill_rates` as an array program.

    ``indptr``/``indices`` is the CSR incidence (flow i holds resources
    ``indices[indptr[i]:indptr[i+1]]``, every flow holds >= 1), ``cap``
    the aggregate rate per (local) resource, ``holds`` the hold count
    per resource.  Bit-identical to the dict reference: each flow's rate
    is the float min over the same ``cap/holds`` shares.  Resources
    with zero holds (dead entries kept in a cached component
    numbering) are skipped by the guarded divide; no pair references
    them, so they never reach the min.
    """
    share = np.divide(cap, holds, out=np.zeros(cap.size), where=holds > 0)
    return np.minimum.reduceat(share[indices], indptr[:-1])


def vector_water_fill(indptr: np.ndarray, indices: np.ndarray,
                      cap: np.ndarray) -> np.ndarray:
    """`engine.water_filling_rates` as an array program.

    Same bottleneck-freeze iteration: each round computes every live
    resource's fair share, pins the flows holding a min-share bottleneck
    at that share, and releases their holds.  The capacity update uses
    `np.subtract.at` — one subtraction *per hold*, unbuffered, exactly
    the reference's sequential ``remaining[r] -= m`` folds — and tie
    grouping uses exact float equality, so the returned rates are
    bitwise equal to the dict reference on any instance.
    """
    nf = indptr.size - 1
    counts = np.diff(indptr)
    pair_flow = np.repeat(np.arange(nf), counts)
    remaining = np.array(cap, dtype=float, copy=True)
    live = np.bincount(indices, minlength=cap.size)
    rates = np.zeros(nf)
    unpinned = np.ones(nf, bool)
    n_left = nf
    # dead resources (live == 0) divide to inf (remaining > 0) or nan
    # (0/0); `fmin.reduce` skips nans and nothing pairs with them, so
    # neither ever reaches the min.  While any flow is unpinned, some
    # resource is live, so m stays finite and each round pins >= 1
    # flow.  A flow's pairs only matter until the round that pins it —
    # pins of already-pinned flows are filtered by `unpinned` — so no
    # per-pair active mask is needed.
    old = np.seterr(divide="ignore", invalid="ignore")
    try:
        while n_left:
            fair = remaining / live
            m = np.fmin.reduce(fair)
            pin = np.zeros(nf, bool)
            pin[pair_flow[fair[indices] == m]] = True
            pin &= unpinned
            rates[pin] = m
            unpinned[pin] = False
            idx = indices[pin[pair_flow]]
            np.subtract.at(remaining, idx, m)
            np.maximum(remaining, 0.0, out=remaining)
            np.subtract.at(live, idx, 1)
            n_left -= int(np.count_nonzero(pin))
    finally:
        np.seterr(**old)
    return rates


# ---------------------------------------------------------------------------
# jax.jit water-fill (optional solver for the array core)
# ---------------------------------------------------------------------------

# built lazily on first use: the jitted kernel + the x64 context manager
_JIT = {}


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def _jit_kernel() -> dict:
    if not _JIT:
        import jax
        from jax import lax
        import jax.numpy as jnp

        def body(carry):
            remaining, live, rates, unpinned, n_left, pair_flow, \
                indices = carry
            # one bottleneck-freeze round, op-for-op the numpy loop:
            # IEEE divides, a nan-skipping min (pure selection), exact
            # float equality for the tie group — so every round's m and
            # pin set match `vector_water_fill` bitwise
            fair = remaining / live
            m = jnp.nanmin(fair)
            hits = (fair[indices] == m).astype(jnp.int64)
            pin = (jnp.zeros(rates.shape[0], jnp.int64)
                   .at[pair_flow].add(hits) > 0) & unpinned
            rates = jnp.where(pin, m, rates)
            unpinned = unpinned & ~pin
            pp = pin[pair_flow].astype(jnp.int64)
            cnt = jnp.zeros(live.shape[0], jnp.int64).at[indices].add(pp)
            # np.subtract.at applies one unbuffered subtraction *per
            # hold*; replicate that exact left fold — k rounds of
            # `rem - m` for a resource with k pinned holds — instead of
            # a fused k*m (which rounds differently)
            remaining = lax.fori_loop(
                0, cnt.max(),
                lambda i, rem: jnp.where(i < cnt, rem - m, rem),
                remaining)
            remaining = jnp.maximum(remaining, 0.0)
            live = live - cnt
            n_left = n_left - pin.sum()
            return (remaining, live, rates, unpinned, n_left, pair_flow,
                    indices)

        def kernel(pair_flow, indices, cap, live0, rates0):
            nf = rates0.shape[0]
            carry = (cap, live0, rates0,
                     jnp.ones(nf, bool), jnp.asarray(nf, jnp.int64),
                     pair_flow, indices)
            out = lax.while_loop(lambda c: c[4] > 0, body, carry)
            return out[2]

        # donate the rates buffer (it is the only output, so its input
        # allocation is reused in place; donating the others would just
        # warn — they don't alias an output)
        # simlint: ok[STATE001] compile-once cache of a pure kernel —
        # write-once per process, never consulted for sim state
        _JIT["fn"] = jax.jit(kernel, donate_argnums=(4,))  # simlint: ok[STATE001] see above
        _JIT["x64"] = lambda: jax.enable_x64(True)  # simlint: ok[STATE001] see above
        _JIT["jnp"] = jnp  # simlint: ok[STATE001] see above
    return _JIT


def vector_water_fill_jit(indptr: np.ndarray, indices: np.ndarray,
                          cap: np.ndarray) -> np.ndarray:
    """`vector_water_fill` with the round loop compiled by ``jax.jit``.

    The kernel replays the numpy allocator's float operation sequence
    on float64 (under `jax.enable_x64`): per-round IEEE
    divides for the fair shares, a selection min, exact-equality tie
    grouping, and the per-hold sequential capacity subtraction — so the
    returned rates are bitwise equal to `vector_water_fill` and the
    solver choice never shows in an event trace.

    To bound recompilation, instances are padded to power-of-two
    (flows, pairs, resources) buckets with one dummy resource of
    infinite capacity held by the padding flows: its fair share is inf,
    which never ties a real round's finite minimum, so the padding pins
    in exactly one extra final round (at rate inf, sliced off) and no
    real round's arithmetic sees it.
    """
    nf = indptr.size - 1
    if nf == 0:
        return np.zeros(0)
    counts = np.diff(indptr)
    pair_flow = np.repeat(np.arange(nf), counts)
    nres = cap.size
    npairs = indices.size
    nf_pad = _next_pow2(nf + 1)
    n_padf = nf_pad - nf              # >= 1 padding flow
    nres_pad = _next_pow2(nres + 1)
    dummy = nres                      # the inf-capacity pad resource
    npairs_pad = _next_pow2(npairs + n_padf)
    extra = npairs_pad - npairs       # >= n_padf padding pairs
    # first padding flow absorbs the surplus pairs, the rest hold one
    pf_full = np.concatenate([
        pair_flow,
        np.full(extra - n_padf + 1, nf, dtype=np.int64),
        np.arange(nf + 1, nf_pad, dtype=np.int64)])
    idx_full = np.concatenate([
        np.asarray(indices, dtype=np.int64),
        np.full(extra, dummy, dtype=np.int64)])
    cap_full = np.concatenate([np.asarray(cap, dtype=float),
                               np.full(nres_pad - nres, np.inf)])
    live0 = np.bincount(idx_full, minlength=nres_pad)
    jit = _jit_kernel()
    jnp = jit["jnp"]
    with jit["x64"]():
        rates = jit["fn"](jnp.asarray(pf_full), jnp.asarray(idx_full),
                          jnp.asarray(cap_full),
                          jnp.asarray(live0),
                          jnp.zeros(nf_pad))
        out = np.asarray(rates)
    return out[:nf]


# ---------------------------------------------------------------------------
# Dict reference core (the original hot loop, verbatim)
# ---------------------------------------------------------------------------


class DictCore:
    """The engine's original pure-Python numeric state, behind the core
    interface.  Every solve recomputes all flows from scratch with the
    dict allocators — O(flows x resources) per event — which is exactly
    what the array core is benchmarked (and bit-compared) against."""

    backend = "legacy"

    def __init__(self, resources: Dict[str, object],
                 alloc_fn: Callable[[dict, dict, dict], dict]):
        self.resources = resources          # name -> Resource, ordered
        self._alloc = alloc_fn
        self._remaining: dict = {}
        self._scale: dict = {}
        self._running: dict = {}            # tid -> resource tuple
        self._busy = {name: 0.0 for name in resources}
        self._delivered = {name: 0.0 for name in resources}
        self._rate: dict = {}
        self._holds: dict = {}
        self._res_index = {name: i for i, name in enumerate(resources)}
        self.n_solves = 0
        self.flows_solved = 0
        self.t_solve_s = 0.0       # wall time per hot-loop phase, for
        self.t_min_dt_s = 0.0      # validate.compare_backends' digest
        self.t_advance_s = 0.0

    # -- per-task progress state -------------------------------------------

    def track(self, tid: str, work: float) -> None:
        self._remaining[tid] = float(work)
        self._scale[tid] = max(float(work), 1.0)

    def remaining_of(self, tid: str) -> float:
        return self._remaining[tid]

    def set_remaining(self, tid: str, value: float) -> None:
        self._remaining[tid] = value

    # -- running-set incidence ---------------------------------------------

    def start(self, tid: str, task) -> None:
        self._running[tid] = task.resources

    def stop(self, tid: str) -> None:
        del self._running[tid]

    # -- the numeric hot loop ----------------------------------------------

    def solve(self) -> None:
        t0 = time.perf_counter()
        holds: dict = {}
        flows: dict = {}
        out: dict = {}
        for tid, res in self._running.items():
            if not res:               # pure delay task
                out[tid] = 1.0
            else:
                flows[tid] = res
                for r in res:
                    holds[r] = holds.get(r, 0) + 1
        # blocked() keeps any task touching a down node out of the
        # running set, so every held resource here is live
        cap = {name: self.resources[name].aggregate_rate(n)
               for name, n in holds.items()}
        out.update(self._alloc(flows, cap, holds))
        self._rate, self._holds = out, holds
        if self._running:
            self.n_solves += 1
            self.flows_solved += len(self._running)
        self.t_solve_s += time.perf_counter() - t0

    def min_dt(self) -> float:
        t0 = time.perf_counter()
        dt = math.inf
        rem = self._remaining
        for tid, r in self._rate.items():
            if r > _EPS:
                dt = min(dt, rem[tid] / r)
        self.t_min_dt_s += time.perf_counter() - t0
        return dt

    def advance(self, dt: float) -> None:
        t0 = time.perf_counter()
        rem = self._remaining
        for tid, r in self._rate.items():
            rem[tid] -= r * dt
            for name in self._running[tid]:
                self._delivered[name] += r * dt
        for name in self._holds:
            self._busy[name] += dt
        self.t_advance_s += time.perf_counter() - t0

    def finished(self) -> list:
        return [tid for tid in self._running
                if self._remaining[tid] <= _EPS * self._scale[tid]]

    # -- end-of-run accounting ---------------------------------------------

    def busy_time(self) -> dict:
        return self._busy

    def delivered(self) -> dict:
        return self._delivered

    def resource_rates(self) -> tuple:
        """Post-`solve` per-resource (delivered rate, hold count)
        arrays over the engine's stable resource order — the flight
        recorder's re-solve-boundary sample.  The dict core
        materializes them from the current flow rates; the array core
        returns its live arrays for free."""
        n = len(self._res_index)
        rates = np.zeros(n)
        holds = np.zeros(n, dtype=np.int64)
        for name, h in self._holds.items():
            holds[self._res_index[name]] = h
        for tid, r in self._rate.items():
            for name in self._running[tid]:
                rates[self._res_index[name]] += r
        return rates, holds

    def stats(self) -> dict:
        return {"backend": self.backend, "solver": "numpy",
                "n_solves": self.n_solves,
                "flows_solved": self.flows_solved,
                "t_solve_s": self.t_solve_s,
                "t_min_dt_s": self.t_min_dt_s,
                "t_advance_s": self.t_advance_s}


# ---------------------------------------------------------------------------
# Incremental array core
# ---------------------------------------------------------------------------


class ArrayCore:
    """Flat-array numeric state with incremental component re-solves.

    Running flows live in slots of dense numpy arrays (``remaining``,
    ``rate``, ...); each slot's resource ids sit in a strided flat
    ``pool`` over the engine's stable resource indexing, so a solve
    gathers its CSR with pure array ops — no per-flow Python.
    `start`/`stop` update hold counts, the cached per-resource
    capacity (`aggregate_rate` is a pure function of the hold count,
    so it is re-evaluated only when the count changes) and mark the
    touched resources dirty; `solve` recomputes rates only for the
    components containing dirty resources.

    Components are tracked with a merge-only union-find over
    resources: `start` unions the flow's resources, `stop` never
    splits.  Membership is therefore an *over*-approximation — a
    historical component may span several current exact components —
    which is safe and still bit-exact, because the solved set is then
    a disjoint union of exact components and solving extra untouched
    components just recomputes their rates to the identical floats
    (see the module docstring's decomposition invariant).  What it
    buys is O(alpha) incidence updates with no per-solve component
    rebuild.  `advance`/`min_dt`/`finished` are whole-array
    operations, so an event step costs O(slots) numpy time plus the
    affected component's solve instead of O(all flows x resources)
    Python time.
    """

    backend = "array"
    _INITIAL_SLOTS = 64
    _INITIAL_STRIDE = 8
    # pseudo-component id for pure delay tasks (no resources, rate 1.0):
    # they belong to no union-find component but must still contribute
    # to the memoized min_dt reduction
    _DELAY = -1

    def __init__(self, resources: Dict[str, object], allocator: str,
                 solver: str = "numpy"):
        self.res_names = list(resources)
        self.res_list = list(resources.values())
        self.res_index = {n: i for i, n in enumerate(self.res_names)}
        self.allocator = allocator
        self.solver = solver
        nres = len(self.res_list)
        self.holds = np.zeros(nres, dtype=np.int64)
        self.cap = np.zeros(nres)           # aggregate_rate @ current holds
        self.inflow = np.zeros(nres)        # sum of member rates
        self._busy = np.zeros(nres)
        self._delivered = np.zeros(nres)
        self.parent = list(range(nres))     # merge-only union-find
        self.comp_flows: dict = {}          # root -> set of running slots
        # root -> (global->local id map, local->global id list): the
        # component's stable local resource numbering, so a
        # single-component solve skips np.unique.  Entries are only
        # ever appended (resources whose holds drop to 0 stay, with
        # capacity 0 and no pairs — harmless to the allocators).
        self.comp_cache: dict = {}
        n = self._INITIAL_SLOTS
        self.stride = self._INITIAL_STRIDE
        self.remaining = np.zeros(n)
        self.rate = np.zeros(n)
        self.eps_scale = np.zeros(n)
        self.active = np.zeros(n, bool)
        self.nres_of = np.zeros(n, dtype=np.int64)
        self.pool = np.zeros(n * self.stride, dtype=np.int64)
        self.slot_tid = [None] * n
        self.free = list(range(n - 1, -1, -1))
        self.tid2slot: dict = {}
        self.rem_map: dict = {}             # remaining while not running
        self.scale_map: dict = {}
        self.dirty_res: set = set()
        # memoized min_dt state: per-component cached (min time-to-
        # finish, core clock when computed); components dirtied by
        # start/stop/set_remaining (their rates or remainings changed
        # out-of-band) are re-evaluated exactly, clean ones only when
        # their conservative lower bound could beat the current best —
        # see `min_dt`
        self.comp_mindt: dict = {}          # root -> (value, clock)
        self._mindt_dirty: set = set()      # roots (or _DELAY) to redo
        self._delay_slots: set = set()      # running no-resource slots
        self._clock = 0.0                   # cumulative advanced time
        self.n_solves = 0
        self.flows_solved = 0
        self.mindt_evals = 0                # components evaluated
        self.mindt_skips = 0                # components bound-skipped
        self.t_solve_s = 0.0                # wall time per hot-loop phase
        self.t_min_dt_s = 0.0
        self.t_advance_s = 0.0

    def _grow(self) -> None:
        old = self.remaining.size
        new = old * 2
        for name in ("remaining", "rate", "eps_scale", "active", "nres_of"):
            arr = getattr(self, name)
            bigger = np.zeros(new, dtype=arr.dtype)
            bigger[:old] = arr
            setattr(self, name, bigger)
        self.pool = np.concatenate(
            [self.pool, np.zeros(old * self.stride, dtype=np.int64)])
        self.slot_tid.extend([None] * old)
        self.free.extend(range(new - 1, old - 1, -1))

    def _widen(self, k: int) -> None:
        """A task holds more resources than the pool stride fits."""
        new = max(k, self.stride * 2)
        nslots = self.remaining.size
        pool = np.zeros(nslots * new, dtype=np.int64)
        pool.reshape(nslots, new)[:, :self.stride] = \
            self.pool.reshape(nslots, self.stride)
        self.pool, self.stride = pool, new

    def _cache_of(self, root: int):
        cache = self.comp_cache.get(root)
        if cache is None:
            cache = self.comp_cache[root] = \
                (np.full(len(self.res_list), -1, dtype=np.int64), [])
        return cache

    def _find(self, r: int) -> int:
        parent = self.parent
        root = r
        while parent[root] != root:
            root = parent[root]
        while parent[r] != root:          # path compression
            parent[r], r = root, parent[r]
        return root

    # -- per-task progress state -------------------------------------------

    def track(self, tid: str, work: float) -> None:
        self.rem_map[tid] = float(work)
        self.scale_map[tid] = max(float(work), 1.0)

    def remaining_of(self, tid: str) -> float:
        s = self.tid2slot.get(tid)
        return float(self.remaining[s]) if s is not None \
            else self.rem_map[tid]

    def set_remaining(self, tid: str, value: float) -> None:
        self.rem_map[tid] = value
        s = self.tid2slot.get(tid)
        if s is not None:
            self.remaining[s] = value
            self._mindt_dirty.add(self._comp_of(s))

    def _comp_of(self, s: int) -> int:
        """The min_dt component a running slot belongs to."""
        if self.nres_of[s]:
            return self._find(int(self.pool[s * self.stride]))
        return self._DELAY

    # -- running-set incidence ---------------------------------------------

    def start(self, tid: str, task) -> None:
        if not self.free:
            self._grow()
        s = self.free.pop()
        self.tid2slot[tid] = s
        self.slot_tid[s] = tid
        self.remaining[s] = self.rem_map[tid]
        self.eps_scale[s] = _EPS * self.scale_map[tid]
        self.active[s] = True
        if task.resources:
            k = len(task.resources)
            if k > self.stride:
                self._widen(k)
            base = s * self.stride
            holds, cap, res_list = self.holds, self.cap, self.res_list
            ridx = [self.res_index[r] for r in task.resources]
            for j, r in enumerate(ridx):
                self.pool[base + j] = r
                holds[r] += 1
                cap[r] = res_list[r].aggregate_rate(int(holds[r]))
            self.nres_of[s] = k
            find = self._find
            root = find(ridx[0])
            for r in ridx[1:]:
                r2 = find(r)
                if r2 != root:
                    small = self.comp_flows.pop(r2, None)
                    merged = self.comp_cache.pop(r2, None)
                    # r2 is no longer a root: its cached component
                    # minimum (if any) now lives under `root`, which is
                    # dirtied below
                    self.comp_mindt.pop(r2, None)
                    self._mindt_dirty.discard(r2)
                    self.parent[r2] = root
                    if small:
                        self.comp_flows.setdefault(root, set()) \
                            .update(small)
                    if merged is not None:
                        cmap, cres = self._cache_of(root)
                        for rr in merged[1]:
                            if cmap[rr] < 0:
                                cmap[rr] = len(cres)
                                cres.append(rr)
            self.comp_flows.setdefault(root, set()).add(s)
            cmap, cres = self._cache_of(root)
            for rr in ridx:
                if cmap[rr] < 0:
                    cmap[rr] = len(cres)
                    cres.append(rr)
            self.dirty_res.update(ridx)
            self._mindt_dirty.add(root)
            self.rate[s] = 0.0            # set by the next solve
        else:
            self.nres_of[s] = 0
            self.rate[s] = 1.0            # pure delay task
            self._delay_slots.add(s)
            self._mindt_dirty.add(self._DELAY)

    def stop(self, tid: str) -> None:
        s = self.tid2slot.pop(tid)
        self.rem_map[tid] = float(self.remaining[s])
        self.active[s] = False
        self.rate[s] = 0.0
        k = int(self.nres_of[s])
        if k:
            base = s * self.stride
            ridx = self.pool[base:base + k].tolist()
            holds, cap, res_list = self.holds, self.cap, self.res_list
            for r in ridx:
                holds[r] -= 1
                cap[r] = res_list[r].aggregate_rate(int(holds[r])) \
                    if holds[r] > 0 else 0.0
            self.dirty_res.update(ridx)
            root = self._find(ridx[0])
            self.comp_flows[root].discard(s)
            self._mindt_dirty.add(root)
            self.nres_of[s] = 0
        else:
            self._delay_slots.discard(s)
            self._mindt_dirty.add(self._DELAY)
        self.slot_tid[s] = None
        self.free.append(s)

    # -- the numeric hot loop ----------------------------------------------

    def solve(self) -> None:
        """Recompute rates for every component touching a dirty resource.

        A removed flow's resources are dirty and their component still
        files its old neighbours; an added flow's resources are dirty
        and its component already files it — so the union of the dirty
        resources' component member sets covers every flow whose rate
        can have changed (plus, with merge-only components, possibly
        whole untouched exact components, which resolve to identical
        floats — see the class docstring).  The gather is pure numpy:
        a ragged strided read of the pool, one `np.unique` for the
        local resource relabelling, and cached capacities."""
        if not self.dirty_res:
            return
        t0 = time.perf_counter()
        find = self._find
        roots = {find(r) for r in self.dirty_res}
        # a dirty resource with no holders left delivers nothing
        self.inflow[np.fromiter(self.dirty_res, dtype=np.int64,
                                count=len(self.dirty_res))] = 0.0
        self.dirty_res.clear()
        # sorted: `roots` is a set of int root ids and its hash order
        # must not pick the concatenation order below (slots are
        # re-sorted anyway, but the invariant is cheap to keep exact)
        live_roots = [rt for rt in sorted(roots)
                      if self.comp_flows.get(rt)]
        if not live_roots:
            self.t_solve_s += time.perf_counter() - t0
            return
        if len(live_roots) == 1:
            g = self.comp_flows[live_roots[0]]
            slots = np.fromiter(g, dtype=np.int64, count=len(g))
        else:
            slots = np.concatenate(
                [np.fromiter(self.comp_flows[rt], dtype=np.int64,
                             count=len(self.comp_flows[rt]))
                 for rt in live_roots])
        slots.sort()
        counts = self.nres_of[slots]
        total = int(counts.sum())
        indptr = np.zeros(slots.size + 1, dtype=np.int64)
        np.cumsum(counts, out=indptr[1:])
        rows = np.repeat(slots * self.stride - indptr[:-1], counts) \
            + np.arange(total)
        if len(live_roots) == 1:
            # the component's cached numbering: one gather, no unique
            cmap, cres = self.comp_cache[live_roots[0]]
            local_res = np.fromiter(cres, dtype=np.int64,
                                    count=len(cres))
            indices = cmap[self.pool[rows]]
        else:
            local_res, indices = np.unique(self.pool[rows],
                                           return_inverse=True)
        cap = self.cap[local_res]
        if self.allocator == "waterfill":
            # the jit round loop is bitwise equal to the numpy one, so
            # routing only large components through it (the dispatch
            # overhead beats the kernel below _JIT_MIN_FLOWS) is
            # invisible in the trace
            if self.solver == "jit" and slots.size >= _JIT_MIN_FLOWS:
                vals = vector_water_fill_jit(indptr, indices, cap)
            else:
                vals = vector_water_fill(indptr, indices, cap)
        else:
            vals = vector_progressive_fill(indptr, indices, cap,
                                           self.holds[local_res])
        self.rate[slots] = vals
        pair_flow = np.repeat(np.arange(slots.size), counts)
        self.inflow[local_res] = np.bincount(indices,
                                             weights=vals[pair_flow],
                                             minlength=local_res.size)
        self.n_solves += 1
        self.flows_solved += slots.size
        self.t_solve_s += time.perf_counter() - t0

    def _comp_min(self, group) -> float:
        """Exact min time-to-finish over one component's slots — the
        same ``remaining / rate`` divides the full-array scan would
        perform, so the partition min is bitwise the global min."""
        if not group:
            return math.inf
        slots = np.fromiter(group, dtype=np.int64, count=len(group))
        r = self.rate[slots]
        mask = r > _EPS
        if not mask.any():
            return math.inf
        return float((self.remaining[slots][mask] / r[mask]).min())

    def min_dt(self) -> float:
        """Memoized global min time-to-finish.

        Per component the core caches ``(value, clock)`` — its exact
        slot-wise minimum and the core clock when it was computed.
        Components dirtied since (start/stop/set_remaining changed
        their rates or remainings out-of-band) are re-evaluated
        exactly.  A *clean* component's slots all advanced at unchanged
        rates, so in exact arithmetic its minimum is ``value -
        elapsed``; in floats it can drift below that by accumulated
        rounding, which the slack term over-covers by many orders of
        magnitude (relative fp drift is ~1e-13 even over millions of
        steps).  Clean components are visited in ascending lower-bound
        order and evaluated exactly only while their bound could still
        beat the best so far — every skipped component provably has a
        larger minimum, so the returned value is *bitwise* the full
        scan's (min is selection, not arithmetic): per-step cost drops
        from O(running) to O(dirty components + near-minimum ones).
        """
        t_in = time.perf_counter()
        cache = self.comp_mindt
        clock = self._clock
        if self._mindt_dirty:
            find = self._find
            for rt0 in self._mindt_dirty:
                rt = rt0 if rt0 == self._DELAY else find(rt0)
                group = self._delay_slots if rt == self._DELAY \
                    else self.comp_flows.get(rt)
                if group:
                    cache[rt] = (self._comp_min(group), clock)
                    self.mindt_evals += 1
                else:
                    cache.pop(rt, None)
            self._mindt_dirty.clear()
        best = math.inf
        stale = []
        for rt, (val, t0) in cache.items():
            elapsed = clock - t0
            if elapsed == 0.0:
                if val < best:
                    best = val
            else:
                lb = val - elapsed - 1e-6 * (abs(val) + elapsed + 1.0)
                stale.append((lb, rt))
        stale.sort()
        for i, (lb, rt) in enumerate(stale):
            if lb >= best:
                self.mindt_skips += len(stale) - i
                break
            group = self._delay_slots if rt == self._DELAY \
                else self.comp_flows[rt]
            val = self._comp_min(group)
            cache[rt] = (val, clock)
            self.mindt_evals += 1
            if val < best:
                best = val
        self.t_min_dt_s += time.perf_counter() - t_in
        return best

    def advance(self, dt: float) -> None:
        # inactive slots carry rate 0, so one fused array op advances
        # exactly the running flows — same per-element float arithmetic
        # as the dict reference's `remaining[tid] -= r * dt`
        t0 = time.perf_counter()
        self.remaining -= self.rate * dt
        self._busy[self.holds > 0] += dt
        self._delivered += self.inflow * dt
        self._clock += dt
        self.t_advance_s += time.perf_counter() - t0

    def finished(self) -> list:
        mask = self.active & (self.remaining <= self.eps_scale)
        return [self.slot_tid[s] for s in np.flatnonzero(mask)]

    # -- end-of-run accounting ---------------------------------------------

    def busy_time(self) -> dict:
        return {name: float(self._busy[i])
                for i, name in enumerate(self.res_names)}

    def delivered(self) -> dict:
        return {name: float(self._delivered[i])
                for i, name in enumerate(self.res_names)}

    def resource_rates(self) -> tuple:
        """Post-`solve` per-resource (delivered rate, hold count)
        arrays over the engine's stable resource order — these are
        the live arrays `advance` integrates, returned by reference
        (callers must not mutate), so the flight recorder's sample is
        exact and costs nothing to produce."""
        return self.inflow, self.holds

    def stats(self) -> dict:
        return {"backend": self.backend, "solver": self.solver,
                "n_solves": self.n_solves,
                "flows_solved": self.flows_solved,
                "mindt_evals": self.mindt_evals,
                "mindt_skips": self.mindt_skips,
                "t_solve_s": self.t_solve_s,
                "t_min_dt_s": self.t_min_dt_s,
                "t_advance_s": self.t_advance_s}


def make_core(backend: str, resources: Dict[str, object], allocator: str,
              alloc_fn: Callable[[dict, dict, dict], dict],
              solver: str = "numpy"):
    """One fresh numeric core per `Engine.run` call."""
    if solver not in SOLVERS:
        raise ValueError(f"unknown solver {solver!r}; "
                         f"expected one of {SOLVERS}")
    if backend == "legacy":
        return DictCore(resources, alloc_fn)
    if backend == "array":
        return ArrayCore(resources, allocator, solver=solver)
    raise ValueError(f"unknown backend {backend!r}; "
                     f"expected one of {BACKENDS}")
