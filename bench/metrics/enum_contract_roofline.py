"""Share of its roofline that the discrete elimination's kernels reach.

Each ``enum_contract_chain`` call runs the forward algorithm over every
step of every sequence of every chain, and each ``enum_contract_chain_vjp``
call the backward recursion of its gradient.  A call's least time is the
larger of its bytes over the HBM bandwidth and its FLOPs over the peak,
with the work of one pass counted as the configuration's
``enum_call_cost`` says (each step's emission and each chain's transition
read once, each step's messages written), whatever the kernel does; the
share is that least time over the calls' device time.  It is read per
call, so a trace the profiler cut short still gives it.  The gradient's
sums over all steps at once are XLA's operations and are not counted.
"""


def read(run):
    seconds, calls = run.trace.kernel("enum_contract_chain")
    if not calls or run.peak is None or seconds <= 0:
        return None
    chains = run.cell.traffic["num_chains"]
    cost = run.cell.model.enum_call_cost(run.cell.config, chains)
    least = max(cost["bytes"] / run.peak["hbm_bytes_per_s"],
                cost["flops"] / run.peak["flops_per_s"])
    return 100.0 * calls * least / seconds
