"""Print what the metadata of a profiler trace holds (each plane's stat
names, and the first events of its lines with the stats of their
metadata, where an operation's ``jax.named_scope`` path is), and how many
of the first device's operations ``benchmark/program_trace.py`` finds a
scope for. ``describe_trace.py`` shows the events' own stats only.

    python3 benchmark/tools/describe_program_trace.py <profile dir or .xplane.pb>
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import program_trace, trace  # noqa: E402

if __name__ == "__main__":
    path = sys.argv[1]
    if os.path.isdir(path):
        path = trace.newest_xplane(path)
    out = program_trace.describe(path, limit=int(
        sys.argv[2]) if len(sys.argv) > 2 else 8)
    pt = program_trace.load(path)
    with_path = sum(1 for o in pt["ops"] if o[3])
    out["first_device"] = {
        "ops": len(pt["ops"]), "ops_with_scope_path": with_path,
        "host_events": len(pt["host"]),
        "host_names": sorted({h[0] for h in pt["host"]}),
        "clock_anchors": program_trace.clock_anchors(pt)}
    print(json.dumps(out, indent=1))
