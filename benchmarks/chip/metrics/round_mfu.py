"""Model FLOPs of every local update in the traced window (the task's
training FLOPs per sample, an image or a sequence, x E x B per update), over
the window times the chip's bf16 peak: the whole round's share of the peak.
fp32 matmuls at the TPU's default precision run on the bf16 MXU path."""


def read(ctx):
    _, n = ctx.module_time(("local_update",))
    if n == 0 or ctx.window_s <= 0:
        return None
    flops = n * ctx.samples_per_update * ctx.train_flops_per_sample
    return 100.0 * flops / (ctx.window_s * ctx.peaks["bf16_flops"])
