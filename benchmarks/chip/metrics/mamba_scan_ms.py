"""Device time of the ops under the scope `mamba/scan` (the selective
scan of every Mamba mixer) per execution of the prefill program
(`prefill`), in ms, by the program's own `jax.named_scope` paths
(`scopes.py`'s reduction, which `kinds/serve_static_hybrid.py` hands over
as `work["scopes"]`).  Nothing where the kind gives no scopes or the trace
no such op."""


def read(r):
    sc = r.work.get("scopes")
    if sc is None:
        return None
    return sc.ms_per_run(r.reduced.module_s, "jit_prefill", "mamba/scan")
