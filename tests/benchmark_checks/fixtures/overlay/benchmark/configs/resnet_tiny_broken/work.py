from benchmark.configs.resnet50.work import *  # noqa: F401,F403
