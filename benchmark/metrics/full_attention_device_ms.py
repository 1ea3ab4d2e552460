"""Kernels: device time a traced step spent under the program's
``full_attention`` scope (the blocks of scores, softmax and values of every
full-attention layer; forward, recomputation and backward together): beside
``window_attention_device_ms`` a layer, it says whether the blocks behind the
window are skipped."""

from benchmark import swa_scopes


def read(run: dict):
    return swa_scopes.scope_ms(run, "full_attention")
