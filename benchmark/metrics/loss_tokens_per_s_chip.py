"""Trainer: tokens that bore a loss (their next token is the same
document's) a second a chip: the program's counter ``lm_loss_tokens_total``
over ``trainer_steps_total`` (both counted once a ``Trainer.step``, from the
host batch), times the steps that completed in the window over its seconds
and the chips."""

from benchmark import program_spans


def read(run: dict):
    loss_tokens = program_spans.counter(run, "lm_loss_tokens_total")
    steps = program_spans.counter(run, "trainer_steps_total")
    if not loss_tokens or not steps:
        return None
    window = run["trainer"]["window"]
    return (loss_tokens / steps * window["steps"] / window["seconds"]
            / run["cell"]["chips"])
