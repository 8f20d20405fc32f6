"""Device calls of the array drain (``ArrayMatchEngine.backend_calls``) per
million check-ins consumed: a count, which repeats exactly for a seed."""


def read(ctx):
    if not ctx["checkins"]:
        return None
    return 1e6 * ctx["backend_calls"] / ctx["checkins"]
