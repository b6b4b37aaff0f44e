"""Print what a profiler trace holds, to read one by hand before trusting the
reduction: planes, their lines, event counts, time covered, commonest names.

    python benchmark/run.py ... --trace 1 --keep-trace <dir>
    python benchmark/trace_dump.py <dir>/<file>.xplane.pb
"""
import collections
import sys

import trace_reduce


def main(path):
    for plane, lines in trace_reduce.load(path).items():
        print("plane %r" % plane)
        for line, events in lines.items():
            if not events:
                continue
            total = sum(e - s for _, s, e in events) / 1e9
            merged = sum(e - s for s, e in trace_reduce.union(
                (s, e) for _, s, e in events)) / 1e9
            span = (max(e for _, _, e in events) - min(s for _, s, _ in events)) / 1e9
            print("  line %r: %d events, sum %.6f s, union %.6f s, over %.6f s, "
                  "first start %d ns" % (line, len(events), total, merged, span,
                                         min(s for _, s, _ in events)))
            by = collections.Counter()
            for n, s, e in events:
                by[n] += e - s
            for n, ns in by.most_common(12):
                print("      %.6f s  x%d  %s" % (
                    ns / 1e9, sum(1 for ev in events if ev[0] == n), n[:110]))


if __name__ == "__main__":
    main(sys.argv[1])
