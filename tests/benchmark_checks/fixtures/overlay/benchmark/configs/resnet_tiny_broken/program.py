"""The timed path broken underneath: a step that returns its state unchanged
(and still reports a loss).  ``correct`` has to come out false."""
from benchmark.configs.resnet50 import program as _sound
from benchmark.configs.resnet50.program import *  # noqa: F401,F403


def build(config, ctx=None):
    trainer = _sound.build(config, ctx)
    real = trainer.train_step

    def frozen(state, batch):
        import jax

        kept = jax.tree_util.tree_map(lambda a: a.copy(), state)
        _new, loss = real(state, batch)     # donates ``state``
        return kept, loss

    trainer.train_step = frozen
    return trainer
