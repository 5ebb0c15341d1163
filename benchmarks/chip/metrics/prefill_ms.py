"""Device time of one execution of the prefill program (`prefill`),
averaged over its executions in the traced window (ms)."""


def read(r):
    t, n = r.reduced.module_s.get("jit_prefill", (0.0, 0))
    return 1e3 * t / n if n else None
