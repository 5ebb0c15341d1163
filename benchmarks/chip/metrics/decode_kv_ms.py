"""Device time of the ops under the scope `attn/kv` per execution of the
decode-step program (`serve_step`), in ms: the cache's slot write and the
kernel wrapper's transposes, pads and slice-back, by the program's own
`jax.named_scope` paths (`scopes.py`, read as `r.scopes`).  The layer
scan's own slicing and write-back of the caches carry no scope and are not
counted.  Nothing when the reading has no scopes or the trace no such op."""


def read(r):
    sc = getattr(r, "scopes", None)
    if sc is None:
        return None
    return sc.ms_per_run(r.reduced.module_s, "jit_serve_step", "attn/kv")
