"""§6 collective traffic: flat vs hierarchical vs compressed schedules.

Compiles in this process on 8 fake CPU host devices (2 pods x 2 data x 2
model) and counts actual HLO collective bytes per tier, comparing against
the analytic traffic model and the paper's "x phi cross-host traffic"
claim.  It needs a process of its own, which it pins to the CPU:

    PYTHONPATH=src python -m benchmarks.bench_collectives
"""
import os
import time

N_DEV = 8


def run():
    import jax
    import jax.numpy as jnp
    from repro.core.collectives import (allreduce_traffic_model,
                                        flat_all_reduce,
                                        hierarchical_all_reduce,
                                        phi_traffic_scaling)
    from repro.launch.hlo_analysis import analyze_collectives
    from repro.launch.mesh import auto_mesh
    if jax.default_backend() != "cpu" or jax.device_count() != N_DEV:
        raise RuntimeError(
            f"bench_collectives compiles for {N_DEV} fake CPU devices, found "
            f"{jax.device_count()} {jax.default_backend()} device(s); run it "
            "as its own process: python -m benchmarks.bench_collectives")
    t0 = time.perf_counter()
    mesh = auto_mesh((2, 2, 2), ("pod", "data", "model"))
    x = jnp.zeros((4, 1 << 16), jnp.float32)
    out = {}
    for name, fn in [("flat", flat_all_reduce),
                     ("hierarchical", hierarchical_all_reduce)]:
        txt = jax.jit(lambda x: fn(x, mesh)).lower(x).compile().as_text()
        c = analyze_collectives(txt, pod_size=4, n_dev=N_DEV)
        out[name] = {"ici": c.ici_bytes, "dcn": c.dcn_bytes}
    nb = x.nbytes // 4
    model_hier = allreduce_traffic_model(nb, n_pods=2, data=2,
                                         schedule="hierarchical")
    model_comp = allreduce_traffic_model(nb, n_pods=2, data=2,
                                         schedule="compressed")
    phi_scaling = {str(phi): phi_traffic_scaling(nb, phi)["ratio"]
                   for phi in (1, 2, 4)}
    us = (time.perf_counter() - t0) * 1e6
    return [
        ("collectives/flat_hlo", us,
         f"ici={out['flat']['ici']} dcn={out['flat']['dcn']}"),
        ("collectives/hierarchical_hlo", us,
         f"ici={out['hierarchical']['ici']} dcn={out['hierarchical']['dcn']}"),
        ("collectives/dcn_reduction", 0.0,
         f"{out['flat']['dcn'] / max(out['hierarchical']['dcn'], 1):.1f}x "
         "less DCN traffic (hierarchical vs flat)"),
        ("collectives/model_compressed_dcn", 0.0,
         f"model_dcn_bytes={model_comp['dcn_bytes']:.0f} "
         f"(4x below fp32 hier {model_hier['dcn_bytes']:.0f})"),
        ("collectives/phi_traffic", 0.0,
         f"cross-host bytes scale {phi_scaling} (paper: x phi)"),
    ]


if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N_DEV}"
    for r in run():
        print(",".join(str(x) for x in r))
