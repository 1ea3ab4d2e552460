"""The fixture configuration shares the resnet50 reference: only sizes differ."""
from benchmark.configs.resnet50.reference import *  # noqa: F401,F403
