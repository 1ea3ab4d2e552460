from benchmark.configs.criteo_widedeep.reference import *  # noqa: F401,F403
