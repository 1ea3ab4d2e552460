"""Compile cache: executables loaded from the persistent cache by the end of
warm-up, as ``compile_cache.stats()["disk_hits"]`` counts them.  (Its
``disk_writes`` counts attempts, not files: PR 21.  Not read.)"""


def read(run: dict):
    return run["trainer"]["cache"]["disk_hits"]
