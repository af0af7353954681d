"""Device time of the leapfrog integrator kernels per useful gradient."""


def read(run):
    seconds, calls = run.trace.kernel("leapfrog_halfstep")
    if not calls or not run.window_grads:
        return None
    return 1e6 * seconds / run.window_grads
