"""HBM bandwidth the decode steps' MoE layers needed, as a share of the
peak (%): the bytes of the held experts each step read (those that got a
token in it, by the program's routing counter) and of the routers, over
the device time of the ops under the scope `mlp/moe` in the decode-step
program (`serve_step`), over the HBM bandwidth.  Nothing where the kind
gives no such bytes or scopes (`kinds/serve_static_hybrid.py` does), or
the trace no such op."""


def read(r):
    sc, nbytes = r.work.get("scopes"), r.work.get("moe_decode_bytes")
    if sc is None or nbytes is None:
        return None
    t = sum(v for k, v in sc.scope_s.get("jit_serve_step", {}).items()
            if k == "mlp/moe" or k.startswith("mlp/moe/"))
    if not t:
        return None
    return 100.0 * nbytes / t / r.peaks["hbm_bytes_per_s"]
