"""A by-hand look at a profiler trace: planes, lines, the heaviest device
events with their stats.  ``python3 benchmarks/look_trace.py <trace_dir>``.
For whoever writes a per-layer metric's regular expression; no metric
reads this."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import trace

if __name__ == "__main__":
    print(trace.summarize(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2
                          else 60))
