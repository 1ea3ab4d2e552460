"""Kernels: device time a traced step spent under the program's
``kda_mixer`` scope (the Kimi Delta Attention mixers whole: the norm, the
nine projections, the three convolutions, the L2 norms and the decay, the
chunked recurrence, the heads' norm and the gate; every KDA layer; forward,
recomputation and backward together)."""

from benchmark import kda_scopes


def read(run: dict):
    return kda_scopes.scope_ms(run, "kda_mixer")
