#!/usr/bin/env python3
"""Where a test run's time went, from its junit file.

    python3 scripts/junit_seconds.py /tmp/_t1.xml [--over 15] [--files 15]

Prints the run's wall and the sum of the cases' own seconds (with
``-n 6`` the ideal wall is a sixth of the sum), the cases by how long
they took, the costliest files (under ``--dist loadfile`` a file is the
unit of scheduling, so the longest file bounds the wall from below) and
every case of ``--over`` seconds or more.  ROADMAP C16 holds tier-1 to
what this prints: a PR that adds cases states their seconds from it.
"""

import argparse
import collections
import xml.etree.ElementTree as ET

BANDS = (60.0, 30.0, 15.0, 5.0)


def read(path):
    """(wall seconds, [(file, case id, seconds, outcome)]) of a junit file."""
    root = ET.parse(path).getroot()
    suites = [root] if root.tag == "testsuite" else root.findall("testsuite")
    wall = sum(float(s.get("time", 0)) for s in suites)
    cases = []
    for suite in suites:
        for case in suite.iter("testcase"):
            outcome = "passed"
            for child in case:
                if child.tag in ("failure", "error", "skipped"):
                    outcome = child.tag
            cases.append((
                case.get("classname", "").rsplit(".", 1)[-1],
                case.get("name", ""),
                float(case.get("time", 0)),
                outcome,
            ))
    return wall, cases


def report(wall, cases, over, files, workers):
    total = sum(c[2] for c in cases)
    outcomes = collections.Counter(c[3] for c in cases)
    share = lambda s: "%3.0f%%" % (100 * s / total if total else 0)
    print("cases %d (%s)  wall %.0f s  case-seconds %.0f s  ideal at -n %d "
          "%.0f s" % (len(cases),
                      ", ".join("%d %s" % (n, o)
                                for o, n in sorted(outcomes.items())),
                      wall, total, workers, total / workers))
    print("\n| cases of | cases | case-seconds | share |")
    print("| --- | --- | --- | --- |")
    for floor in BANDS:
        took = [c[2] for c in cases if c[2] >= floor]
        print("| %g s or more | %d | %.0f | %s |"
              % (floor, len(took), sum(took), share(sum(took))))
    rest = [c[2] for c in cases if c[2] < BANDS[-1]]
    print("| under %g s | %d | %.0f | %s |"
          % (BANDS[-1], len(rest), sum(rest), share(sum(rest))))

    by_file = collections.defaultdict(list)
    for name, case, seconds, _ in cases:
        by_file[name].append((seconds, case))
    ranked = sorted(by_file.items(), key=lambda kv: -sum(s for s, _ in kv[1]))
    print("\n| file | cases | case-seconds | the costliest |")
    print("| --- | --- | --- | --- |")
    for name, took in ranked[:files]:
        top = sorted(took, reverse=True)[:3]
        print("| `%s` | %d | %.0f | %s |"
              % (name, len(took), sum(s for s, _ in took),
                 ", ".join("`%s` %.0f" % (c, s) for s, c in top)))
    if len(ranked) > files:
        others = ranked[files:]
        print("| the other %d files | %d | %.0f | |"
              % (len(others), sum(len(t) for _, t in others),
                 sum(s for _, t in others for s, _ in t)))

    slow = sorted((c for c in cases if c[2] >= over), key=lambda c: -c[2])
    print("\n%d cases of %g s or more, %.0f s:"
          % (len(slow), over, sum(c[2] for c in slow)))
    for name, case, seconds, _ in slow:
        print("  %7.1f  %s::%s" % (seconds, name, case))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("junit", help="the file --junitxml wrote")
    ap.add_argument("--over", type=float, default=15.0,
                    help="list every case of this many seconds or more")
    ap.add_argument("--files", type=int, default=15,
                    help="rows of the per-file table")
    ap.add_argument("--workers", type=int, default=6,
                    help="the run's -n, for the ideal wall")
    args = ap.parse_args(argv)
    wall, cases = read(args.junit)
    report(wall, cases, args.over, args.files, args.workers)


if __name__ == "__main__":
    main()
