"""The flash-attention forward kernel's share of its roofline in prefill
(%): the least time of its calls, each the larger of the causal (and
windowed) FLOPs over peak and q, k, v, o bytes over HBM bandwidth
(`flops.flash_fwd`), over the kernel's measured time.  Nothing when the
kernel is not in the trace."""

KERNEL = "flash_attention"


def read(r):
    t, n = r.reduced.kernel_s.get(KERNEL, (0.0, 0))
    calls = r.work["kernels"].get(KERNEL, [])
    if not n or not calls:
        return None
    pf, bw = r.peaks["bf16_flops_per_s"], r.peaks["hbm_bytes_per_s"]
    least = sum(k * max(f / pf, b / bw) for f, b, k in calls)
    return 100.0 * least / t
