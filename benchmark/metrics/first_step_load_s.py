"""Compile cache: seconds of step 1's ``trainer.dispatch`` under
``jit.compile`` spans.  On a warm start that is the module's serialisation for
the cache key, the persistent cache's read (the span's ``retrieval_s``) and
the executable's load; on a cold one XLA's compile and the entry's write
(``benchmark/start_spans.py``)."""

from benchmark import start_spans


def read(run: dict):
    return start_spans.first_step(run, "load_s")
