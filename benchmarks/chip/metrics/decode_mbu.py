"""HBM bandwidth the decode steps needed, as a share of the peak (%): the
bytes each step must move (every weight once, each layer's K and V of the
attended positions once; `flops.decode_step_bytes`) over the device time
of the decode-step program, over the HBM bandwidth."""


def read(r):
    t, n = r.reduced.module_s.get("jit_serve_step", (0.0, 0))
    if not n:
        return None
    return 100.0 * r.work["decode_bytes"] / t / r.peaks["hbm_bytes_per_s"]
