"""Share of its roofline that the fused GLM kernel reaches.

Each ``glm_potential_grad`` call serves all the cell's chains at once.  Its
least time is the larger of the call's bytes over the HBM bandwidth and its
FLOPs over the peak, with the work counted as the configuration's
``glm_call_cost`` says (x and y read once per call), whatever the kernel
does; the share is that least time over the calls' device time.
"""


def read(run):
    seconds, calls = run.trace.kernel("glm_potential_grad")
    if not calls or run.peak is None or seconds <= 0:
        return None
    chains = run.cell.traffic["num_chains"]
    cost = run.cell.model.glm_call_cost(run.cell.config, chains)
    least = max(cost["bytes"] / run.peak["hbm_bytes_per_s"],
                cost["flops"] / run.peak["flops_per_s"])
    return 100.0 * calls * least / seconds
