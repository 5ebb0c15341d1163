"""Device time of the ops under the scope `attn/kv` per execution of the
prefill program (`prefill`), in ms: the cache fill and the flash kernel
wrapper's pads, transposes and slice-back, by the program's own
`jax.named_scope` paths (`scopes.py`, read as `r.scopes`).  The layer
scan's own slicing and write-back of the caches carry no scope and are not
counted.  Nothing when the reading has no scopes or the trace no such op."""


def read(r):
    sc = getattr(r, "scopes", None)
    if sc is None:
        return None
    return sc.ms_per_run(r.reduced.module_s, "jit_prefill", "attn/kv")
