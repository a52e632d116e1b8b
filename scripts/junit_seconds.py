#!/usr/bin/env python3
"""Where a test run's time went, from its junit file.

    python3 scripts/junit_seconds.py /tmp/_t1.xml [--over 15] [--files 15]
    python3 scripts/junit_seconds.py /tmp/_t1.xml --against parent.xml

Prints the run's wall and the sum of the cases' own seconds (with
``-n 6`` the ideal wall is a sixth of the sum), the cases by how long
they took, the costliest files (under ``--dist loadfile`` a file is the
unit of scheduling, so the longest file bounds the wall from below) and
every case of ``--over`` seconds or more.  ROADMAP C16 holds tier-1 to
what this prints: a PR that adds cases states their seconds from it.
``--against`` reads a second junit file, the parent's, and prints what
a PR that moves tests is held to: the parent's cases the run lacks (by
file and name), the new ones, and each file's case-seconds side by
side.
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


def against(cases, parents, files):
    """What a run's cases are beside the parent's: lost, new, and each
    file's case-seconds in both."""
    ours = {c[:2]: c for c in cases}
    theirs = {c[:2]: c for c in parents}
    for title, only, other in (("the parent's cases this run lacks", theirs,
                                ours), ("new cases", ours, theirs)):
        names = sorted(set(only) - set(other))
        print("\n%s: %d" % (title, len(names)))
        for name in names:
            print("  %7.1f  %s::%s" % (only[name][2], *name))
    unpassed = sorted(k for k, c in ours.items() if c[3] != "passed"
                      and theirs.get(k, c)[3] == "passed")
    print("\ncases that passed in the parent's and do not here: %d"
          % len(unpassed))
    for name in unpassed:
        print("  %s::%s (%s)" % (*name, ours[name][3]))
    took = collections.defaultdict(lambda: [0, 0.0, 0, 0.0])
    for side, run in ((0, parents), (2, cases)):
        for name, _, seconds, _ in run:
            took[name][side] += 1
            took[name][side + 1] += seconds
    ranked = sorted(took.items(), key=lambda kv: -max(kv[1][1], kv[1][3]))
    print("\n| file | parent's cases | case-seconds | cases | case-seconds "
          "| change |")
    print("| --- | --- | --- | --- | --- | --- |")
    rows = [("`%s`" % name, took) for name, took in ranked[:files]]
    for name, some in (("the other %d files" % len(ranked[files:]),
                        ranked[files:]), ("all", ranked)):
        rows.append((name, [sum(t[i] for _, t in some) for i in range(4)]))
    for name, (n0, s0, n1, s1) in rows:
        print("| %s | %d | %.0f | %d | %.0f | %+.0f |"
              % (name, n0, s0, n1, s1, s1 - s0))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("junit", help="the file --junitxml wrote")
    ap.add_argument("--over", type=float, default=15.0,
                    help="list every case of this many seconds or more")
    ap.add_argument("--files", type=int, default=15,
                    help="rows of the per-file table")
    ap.add_argument("--workers", type=int, default=6,
                    help="the run's -n, for the ideal wall")
    ap.add_argument("--against", metavar="JUNIT",
                    help="the parent's junit file: its cases that this "
                    "run lacks, the new ones, each file's seconds in both")
    args = ap.parse_args(argv)
    wall, cases = read(args.junit)
    report(wall, cases, args.over, args.files, args.workers)
    if args.against:
        against(cases, read(args.against)[1], args.files)


if __name__ == "__main__":
    main()
