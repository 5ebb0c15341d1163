"""The decode-attention kernel's share of its roofline (%): the least time
its calls could take on the chip, each the larger of FLOPs over peak and
bytes over HBM bandwidth at the true cache size (`flops.decode_attn`),
over the kernel's measured time.  Nothing when the kernel is not in the
trace."""

KERNEL = "decode_attention"


def read(r):
    t, n = r.reduced.kernel_s.get(KERNEL, (0.0, 0))
    calls = r.work["kernels"].get(KERNEL, [])
    if not n or not calls:
        return None
    pf, bw = r.peaks["bf16_flops_per_s"], r.peaks["hbm_bytes_per_s"]
    least = sum(k * max(f / pf, b / bw) for f, b, k in calls)
    return 100.0 * least / t
