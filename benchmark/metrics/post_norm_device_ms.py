"""Kernels: device time a traced step spent under the program's
``post_norm`` scope (both post-norms of every layer — what attention and the
feed-forward add to the residual stream, normed — and the sums into the
stream; forward, recomputation and backward together)."""

from benchmark import afmoe_scopes


def read(run: dict):
    return afmoe_scopes.scope_ms(run, "post_norm")
