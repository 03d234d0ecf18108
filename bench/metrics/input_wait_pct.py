"""Time the training loop spent blocked on its next batch
(``engine.Pipeline``'s ``PipelineStats.wait_s``) over the timed window, in
percent. The window and not the pipeline's own pass: the loop dispatches
ahead of the device, so its pass ends before the window does."""


def read(ctx):
    stats = ctx.pipeline_stats
    if stats is None or ctx.window_s <= 0:
        return None
    return 100.0 * stats.wait_s / ctx.window_s
