"""Share of the chip's peak FLOP/s that the traced fit's useful gradients
needed: gradients x the configuration's FLOPs per gradient, over the fit's
traced span x the peak."""


def read(run):
    if run.peak is None or run.trace.span_s <= 0 or not run.useful_grads:
        return None
    flops = run.useful_grads * run.cell.model.flops_per_grad(run.cell.config)
    return 100.0 * flops / (run.trace.span_s * run.peak["flops_per_s"])
