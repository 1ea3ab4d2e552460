from benchmark.configs.criteo_widedeep.work import *  # noqa: F401,F403
