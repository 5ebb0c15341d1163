"""Model FLOPs of the traced batch (prefill over the prompts, every decode
step, attention over the positions attended, the output head only where
its logits are used) over the traced window, as a share of the chips'
bf16 peak (%)."""


def read(r):
    peak = r.chips * r.peaks["bf16_flops_per_s"]
    return 100.0 * r.work["model_flops"] / r.reduced.window_s / peak
