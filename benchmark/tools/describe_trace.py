"""Print what a profiler trace holds (planes, lines, first events with
their stats), for reading one by hand before trusting a reducer.

    python3 benchmark/tools/describe_trace.py <profile dir or .xplane.pb>
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import trace  # noqa: E402

if __name__ == "__main__":
    path = sys.argv[1]
    if os.path.isdir(path):
        path = trace.newest_xplane(path)
    print(json.dumps(trace.describe(path, limit=int(
        sys.argv[2]) if len(sys.argv) > 2 else 12), indent=1))
