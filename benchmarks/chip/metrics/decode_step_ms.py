"""Device time of one execution of the decode-step program (`serve_step`),
averaged over the traced batch's steps (ms)."""


def read(r):
    t, n = r.reduced.module_s.get("jit_serve_step", (0.0, 0))
    return 1e3 * t / n if n else None
