"""Feed planes: the driver's calls (``drive``) and the trainer's iterator
(``open_feed``) of one way of feeding a Trainer, found by name."""


class Item:
    """One staged batch with what the benchmark keeps of it.  ``batch`` is
    None for a short batch the loop must drop (its rows still counted)."""

    def __init__(self, batch, ids, nbytes):
        self.batch, self.ids = batch, ids
        self.rows, self.nbytes = len(ids), nbytes
