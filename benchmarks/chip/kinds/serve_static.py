"""Serve traffic: a closed loop of static batches.

`batch` clients each send a prompt of `prompt_len` token ids, drawn
uniformly from the vocabulary with the run's seed, and read greedy tokens
up to an answer length of their own; then all send their next prompt.  The
program has no request queue (it serves one static batch at a time), so a
batch is the unit of submission, and its prompts share one length (the
cache has one write position per batch).  Answer lengths follow a
lognormal law (`output_median`, `output_sigma`, capped at
`max_new_tokens`): every batch holds the same `batch` quantiles of it, in
an order drawn from the seed, and runs until its longest answer is done,
as a static batch does.  So every seed gives the same sizes; only token
ids and the order of the lengths differ.

What the window drives is the program's own serving path, built as
`repro.launch.serve.serve` builds it: `make_prefill` and `make_serve_step`
with the Pallas kernels, compiled ahead of time with the caches donated,
over caches from `init_caches`.  Prompts reach the device through the
program's `Prefetcher`.  Tokens are streamed as a server streams them:
the decode steps are dispatched up to `AHEAD_STEPS` ahead of the token the
host waits for (a step takes its input token from the device), the tokens
already made are read between two dispatches, and each token is stamped
when it reaches the host.  So a short stall of the host
delays the stamps but not the device, which works through the steps in
flight; a batch's next prompts are sent only once its last token is read,
as the clients of a closed loop send them.

The window opens at the submission of the first batch, so the phase of
batch boundaries is the same in every run.  When `seconds` have passed, no
further step is sent: the steps in flight are waited for, and the window
closes when the last of their tokens reaches the host, so that all the work
sent counts over all the time it took.  The batch in flight at the close
then runs to its end, outside the measurement, so that every request
submitted in the window is answered and can be checked.

With `trace` on, the profiler records exactly one batch, the first, and
the run ends after it.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import math
import resource
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

SPAN = jax.profiler.TraceAnnotation
HOST_CLOCK_MIN_S = 0.25   # shortest interval read from the host clock
AHEAD_STEPS = 96          # decode steps in flight: a few seconds' work


@dataclasses.dataclass
class Batch:
    index: int
    submitted: float               # host clock, s
    prefill_dispatched: float      # host clock when the prefill call returned
    marks: np.ndarray              # (G, 2) host clock per token: when the
    #                                call that makes it returned, when it
    #                                reached the host
    tokens: np.ndarray             # (B, G) tokens the program produced
    lengths: np.ndarray            # (B,) answer length each request asked for
    n_window: int                  # tokens of each row sent in the window

    @property
    def times(self) -> np.ndarray:
        return self.marks[:, 1]


def answer_lengths(traffic: dict) -> np.ndarray:
    """The `batch` quantiles of the answer-length law, ascending."""
    B = traffic["batch"]
    z = [statistics.NormalDist().inv_cdf((i + 0.5) / B) for i in range(B)]
    g = [round(traffic["output_median"] * math.exp(traffic["output_sigma"] * x))
         for x in z]
    return np.clip(g, 1, traffic["max_new_tokens"]).astype(np.int64)


def batch_lengths(traffic: dict, seed: int, index: int) -> np.ndarray:
    """Answer lengths of one batch's requests, in the seed's order."""
    rng = np.random.default_rng([seed, 3, index])
    return rng.permutation(answer_lengths(traffic))


def _first_token(logits):
    return jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]


class Server:
    """The program's serving path for one configuration and traffic mix."""

    def __init__(self, c: dict, traffic: dict, params, *, model):
        from repro.models import model as M
        from repro.train.steps import make_prefill, make_serve_step
        self.c, self.params, self.model = c, params, model
        self.B, self.P = traffic["batch"], traffic["prompt_len"]
        self.G = int(answer_lengths(traffic).max())    # steps of a batch
        cfg = model.program_config(c)
        self.caches = jax.jit(lambda: M.init_caches(
            cfg, self.B, self.P + self.G, tp=1))()
        sds = jax.ShapeDtypeStruct
        self.prefill = jax.jit(make_prefill(cfg, use_pallas=True),
                               donate_argnums=(1,)).lower(
            params, self.caches,
            {"tokens": sds((self.B, self.P), jnp.int32)}).compile()
        self.step = jax.jit(make_serve_step(cfg, use_pallas=True),
                            donate_argnums=(1,)).lower(
            params, self.caches, sds((self.B, 1), jnp.int32)).compile()
        self.first = jax.jit(_first_token)

    def warm(self) -> None:
        """One prefill and two decode steps at the timed shapes."""
        z = jax.device_put(np.zeros((self.B, self.P), np.int32))
        logits, self.caches = self.prefill(self.params, self._reset(),
                                           {"tokens": z})
        tok = self.first(logits)
        for _ in range(2):
            tok, self.caches = self.step(self.params, self.caches, tok)
        np.asarray(tok)

    def _reset(self) -> dict:
        """The caches with the write position back at 0; stale slots of the
        previous batch lie past the position and are masked."""
        return {"index": jax.device_put(np.zeros((), np.int32)),
                "layers": self.caches["layers"]}

    def serve_batch(self, index, prompts_dev, lengths,
                    deadline=math.inf) -> Batch:
        """Serve one batch of prompts already on the device; it runs
        `self.G` steps, for its longest answer.  The first step not yet
        sent when the host clock passes `deadline` ends the window: the
        tokens in flight are read, and the rest of the batch is served
        after."""
        submitted = time.perf_counter()
        with SPAN("bench.prefill"):
            logits, self.caches = self.prefill(
                self.params, self._reset(), {"tokens": prompts_dev})
            tok = self.first(logits)
        G = self.G
        marks = np.zeros((G, 2))
        marks[0, 0] = time.perf_counter()
        toks = np.zeros((self.B, G), np.int32)
        flight = collections.deque([(0, tok)])
        n_window = G

        def read():
            k, t = flight.popleft()
            with SPAN("bench.token_read"):
                toks[:, k] = np.asarray(t)[:, 0]
            marks[k, 1] = time.perf_counter()

        for k in range(1, G):
            if n_window == G and time.perf_counter() >= deadline:
                n_window = k
                while flight:
                    read()
            while flight and flight[0][1].is_ready():   # stamp it now
                read()
            with SPAN("bench.decode_step"):
                tok, self.caches = self.step(self.params, self.caches, tok)
            marks[k, 0] = time.perf_counter()
            flight.append((k, tok))
            if len(flight) > AHEAD_STEPS:
                read()
        while flight:
            read()
        return Batch(index, submitted, marks[0, 0], marks, toks, lengths,
                     n_window)

    def release(self) -> None:
        self.caches = None


def prompt_source(seed, B, P, vocab, model):
    from repro.data.pipeline import Prefetcher

    def gen():
        i = 0
        while True:
            yield model.prompts(seed, i, B, P, vocab)
            i += 1

    def put(x):
        with SPAN("bench.input_put"):
            return jax.device_put(x)
    return Prefetcher(gen(), depth=2, put_fn=put)


def serve_window(server: Server, seed: int, seconds: float, traffic: dict,
                 trace_dir=None):
    """The measured loop.  Returns (batches, window open, window close):
    the close is when the last token sent in the window reached the
    host."""
    c = server.c
    src = prompt_source(seed, server.B, server.P, c["vocab_size"],
                         server.model)
    batches = []
    if trace_dir is not None:
        first = next(src)
        jax.profiler.start_trace(str(trace_dir))
        b = server.serve_batch(0, first, batch_lengths(traffic, seed, 0))
        jax.profiler.stop_trace()
        return [b], b.submitted, b.times[-1]
    t_open = time.perf_counter()
    deadline = t_open + seconds
    i = 0
    while time.perf_counter() < deadline:
        batches.append(server.serve_batch(
            i, next(src), batch_lengths(traffic, seed, i), deadline))
        i += 1
    last = batches[-1]
    return batches, t_open, last.times[last.n_window - 1]


def e2e_metrics(batches, t_open, t_close) -> dict:
    """End-to-end metrics over the window from `t_open` to `t_close`.  A
    request's tokens are the first `length` its batch produced; those sent
    in the window count."""
    tokens, ttft, tpot = 0, [], []
    for b in batches:
        own = np.minimum(b.lengths, b.n_window)
        tokens += int(own.sum())
        ttft += [(b.times[0] - b.submitted) * 1e3] * len(b.lengths)
        for m in own:
            span = b.times[m - 1] - b.times[0] if m >= 2 else 0.0
            if span >= HOST_CLOCK_MIN_S:
                tpot.append(span / (m - 1) * 1e3)
    return {
        "serve_tokens_per_s": tokens / (t_close - t_open),
        "ttft_p95_ms": float(np.percentile(ttft, 95)),
        "tpot_p95_ms": float(np.percentile(tpot, 95)) if tpot else None,
        "requests": len(ttft), "tpot_requests": len(tpot),
        "batches": len(batches), "window_s": t_close - t_open,
    }


def host_gaps(batches, top=3) -> dict:
    """Where the host clock lost time in the window: the longest intervals
    between consecutive tokens of a batch against the median one, how many
    exceed twice the median, and the longest call of a batch's steps."""
    rows = []
    for b in batches:
        t = b.times[:b.n_window]
        rows += [(t[k] - t[k - 1], b.index, k) for k in range(1, len(t))]
    if not rows:
        return {}
    med = statistics.median(r[0] for r in rows)
    worst = sorted(rows, reverse=True)[:top]
    return {
        "token_interval_median_ms": med * 1e3,
        "intervals_over_twice_median": int(sum(r[0] > 2 * med
                                               for r in rows)),
        "longest": [{"batch": i, "step": k, "interval_ms": g * 1e3}
                    for g, i, k in worst],
        "call_max_ms": max(
            float(np.diff(b.marks[:, 0]).max(initial=0.0)) * 1e3
            for b in batches),
        "prefill_call_max_ms": max(
            (b.prefill_dispatched - b.submitted) * 1e3 for b in batches),
    }


def usage() -> tuple:
    """(wall, process CPU seconds, involuntary switches, major faults)."""
    r = resource.getrusage(resource.RUSAGE_SELF)
    return (time.perf_counter(), time.process_time(), r.ru_nivcsw,
            r.ru_majflt)


def traced_work(c: dict, traffic: dict, flops) -> dict:
    """What one batch asks of the device, counted from shapes.  Model
    FLOPs count the prompts and each request's own answer tokens; the
    steps' bytes and the kernels' work count every row, as the step runs
    them."""
    B, P = traffic["batch"], traffic["prompt_len"]
    lengths = answer_lengths(traffic)
    G = int(lengths.max())
    L, D = c["num_hidden_layers"], c["hidden_size"]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    d = c.get("head_dim") or D // H
    w = c.get("sliding_window")
    work = {"model_flops": flops.forward_flops(c, B, P), "decode_bytes": 0,
            "kernels": {"flash_attention": [
                (*flops.flash_fwd(B, P, H, K, d, w), L)],
                "decode_attention": []}}
    for k in range(1, G):                  # step k writes position P+k-1
        attended = min(P + k, w) if w else P + k
        asked = int((lengths > k).sum())  # rows whose answer needs token k
        work["model_flops"] += flops.forward_flops(c, asked, 1,
                                                   past=P + k - 1)
        work["decode_bytes"] += flops.decode_step_bytes(c, B, attended)
        work["kernels"]["decode_attention"].append(
            (*flops.decode_attn(B, attended, H, K, d), L))
    return work


def sample_rows(batches, seed, traffic, c, model) -> tuple:
    """A seeded sample of the answered requests, one from each of
    `check_requests` equal slices of the batch's rows (so that a fault in
    part of a batch is sampled): (prompt + produced tokens but the last,
    (n, P + G - 1)), and every token the program produced for them (n, G),
    those past the request's own answer included."""
    B, P = traffic["batch"], traffic["prompt_len"]
    n = traffic["check_requests"]
    done = batches
    rng = np.random.default_rng([seed, 2])
    rows, served = [], []
    for i in range(n):
        b = done[rng.integers(len(done))]
        r = int(rng.integers(i * B // n, (i + 1) * B // n))
        prompt = model.prompts(seed, b.index, B, P, c["vocab_size"])[r]
        rows.append(np.concatenate([prompt, b.tokens[r, :-1]]))
        served.append(np.clip(b.tokens[r], 0, c["vocab_size"] - 1))
    return np.stack(rows), np.stack(served)


def check(batches, seed, traffic, c, params, model, reference) -> dict:
    """Compare served tokens of a seeded sample of the answered requests
    with the float32 reference; see `reference.widest_gap`."""
    bad = int(sum(((b.tokens < 0) | (b.tokens >= c["vocab_size"])).any(1)
                  .sum() for b in batches))
    rows, served = sample_rows(batches, seed, traffic, c, model)
    ref = reference.logits(params, c, rows, first=traffic["prompt_len"] - 1)
    return {"checked_tokens": served.size, "bad_requests": bad,
            "max_logit_gap": reference.widest_gap(ref, served)}


def run(cell, mods) -> dict:
    """One run of a serve cell; see `run.py` for what it returns."""
    model, reference, flops = mods.model, mods.reference, mods.flops
    c, traffic = cell.config, cell.traffic
    t = [time.perf_counter()]
    params = model.make_weights(c, cell.seed)
    jax.block_until_ready(params)
    t.append(time.perf_counter())
    server = Server(c, traffic, params, model=model)
    t.append(time.perf_counter())
    server.warm()
    # what set-up made lives to the end of the run: keep it out of the
    # collector's full passes, which otherwise stall a decode step now and
    # then by ~80 ms on the chip
    gc.collect()
    gc.freeze()
    t.append(time.perf_counter())
    out = {"setup_end": t[-1]}
    u0 = usage()
    with mods.count_compiles() as compiles:
        batches, t_open, t_close = serve_window(
            server, cell.seed, cell.seconds, traffic, cell.trace_dir)
    u1 = usage()
    out["compiles_in_window"] = compiles.n
    out["memory_peak_bytes"] = mods.memory_peak()
    server.release()
    if cell.trace_dir is None:
        out["e2e"] = e2e_metrics(batches, t_open, t_close)
    else:
        out["work"] = traced_work(c, traffic, flops)
    out["host"] = dict(host_gaps(batches), **dict(zip(
        ("wall_s", "cpu_s", "involuntary_switches", "major_faults"),
        (b - a for a, b in zip(u0, u1)))))
    out["attempted"] = traffic["batch"] * len(batches)
    t.append(time.perf_counter())
    out["checks"] = check(batches, cell.seed, traffic, c, params, model,
                          reference)
    out["failed"] = out["checks"]["bad_requests"]
    out["seconds"] = {"weights": t[1] - t[0], "compile": t[2] - t[1],
                      "warm": t[3] - t[2], "window_and_tail": t[4] - t[3],
                      "check": time.perf_counter() - t[4]}
    return out
