"""Model FLOPs of the images completed in the window over the elapsed
window times the chip's bf16 peak, in %."""


def read(run):
    done = run.window_batches()
    if not done or run.window_close <= 0 or not run.peak:
        return None
    flops = sum(b.flops for b in done)
    return 100.0 * flops / (run.window_close * run.peak["bf16_flops_per_s"])
