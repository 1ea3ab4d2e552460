"""Drive one run of a cell past the harness's look for a chip (tests only).

    python run_cell.py <tree> <workload> <seed> <seconds> <trace>

``<tree>`` is a copy of the repository's ``benchmark/`` with the fixture
overlay on top and the fixture ``BENCHMARK.json`` beside it.
"""
import json
import sys

tree = sys.argv[1]
sys.path.insert(0, tree)
from benchmark import run  # noqa: E402

args = run.parse_args(["--workload", sys.argv[2], "--seed", sys.argv[3],
                       "--seconds", sys.argv[4], "--trace", sys.argv[5]])
print(json.dumps(run.run_cell(args, require_chip=False)))
