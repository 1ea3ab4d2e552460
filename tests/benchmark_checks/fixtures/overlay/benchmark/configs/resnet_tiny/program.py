from benchmark.configs.resnet50.program import *  # noqa: F401,F403
