from benchmark.configs.criteo_widedeep.program import *  # noqa: F401,F403
