#!/usr/bin/env python3
"""Where the tier-1 suite's seconds go: one table from one or two runs.

    python scripts/tier1_clock.py COLD.xml COLD.log [WARM.xml WARM.log]

Each run is the driver's command (`commands` in the builder's
TESTS_LAST_RUN.json) with `-v --durations=0 --durations-min=1.0`
instead of `-q`: the junit XML gives every test's seconds, and the
`-v` log's `[gwN] ... PASSED tests/...` lines say which worker ran it
and in what order.  Printed as markdown for docs/dev-guide.md ("The
tier-1 suite's clock"): per run the wall, the serial total and each
worker's busy seconds with the file it ended on; per file its tests,
its seconds in each run, the second at which its worker started it,
and its longest test.  Host seconds of a CPU run: nothing of the chip.
"""
from __future__ import annotations

import collections
import re
import sys
import xml.etree.ElementTree as ET

_DONE = re.compile(r"\[(gw\d+)\] \[ *\d+%\] [A-Z]+ (tests/[^:]+)::(\S+)")


def read_run(xml_path: str, log_path: str) -> dict:
    suite = ET.parse(xml_path).getroot().find("testsuite")
    seconds = {}             # (file, test name) -> seconds
    for case in suite.iter("testcase"):
        # classname is `tests.test_x` or `tests.test_x.Class`; the log
        # says `tests/test_x.py::Class::name`
        pkg, module, *classes = case.get("classname").split(".")
        seconds[(f"{pkg}/{module}.py",
                 "::".join(classes + [case.get("name")]))] = float(
                     case.get("time"))
    files = collections.defaultdict(
        lambda: {"tests": 0, "s": 0.0, "longest": ("", 0.0)})
    for (path, name), s in seconds.items():
        f = files[path]
        f["tests"] += 1
        f["s"] += s
        if s > f["longest"][1]:
            f["longest"] = (name, s)
    # a worker's clock: the running sum of the tests it has finished
    # (collection and idle waits are not in it)
    busy = collections.Counter()
    last_file = {}
    with open(log_path) as log:
        for line in log:
            m = _DONE.search(line)
            if m is None:
                continue
            worker, path, name = m.groups()
            f = files[path]
            f.setdefault("worker", worker)
            f.setdefault("start", busy[worker])
            busy[worker] += seconds[(path, name)]
            last_file[worker] = path
    return {"wall": float(suite.get("time")),
            "passed": int(suite.get("tests")) - sum(
                int(suite.get(k)) for k in ("errors", "failures", "skipped")),
            "files": files, "busy": busy, "last_file": last_file}


def main(argv: list) -> int:
    if len(argv) not in (3, 5):
        print(__doc__, file=sys.stderr)
        return 2
    runs = [read_run(argv[i], argv[i + 1]) for i in range(1, len(argv), 2)]
    names = ["cold", "warm"][:len(runs)]
    for name, run in zip(names, runs):
        serial = sum(f["s"] for f in run["files"].values())
        workers = len(run["busy"])
        print(f"**{name}**: {run['passed']} passed, wall {run['wall']:.0f} s,"
              f" serial total {serial:.0f} s ({serial / workers:.0f} s a"
              f" worker of {workers}); workers, busy seconds and the file"
              f" each ended on: "
              + "; ".join(f"{w} {run['busy'][w]:.0f} s `{run['last_file'][w]}`"
                          for w in sorted(run["busy"])) + "\n")
    head = (["File", "Tests"] + [f"{n} s" for n in names]
            + [f"started at ({n})" for n in names]
            + [f"Longest test ({names[0]})"])
    print("| " + " | ".join(head) + " |")
    print("|" + " --- |" * len(head))
    first = runs[0]["files"]
    for path in sorted(first, key=lambda p: -first[p]["s"]):
        per_run = [run["files"].get(path, {}) for run in runs]
        name, s = first[path]["longest"]
        row = ([f"`{path}`", str(first[path]["tests"])]
               + [f"{f.get('s', 0.0):.0f}" for f in per_run]
               + [f"{f.get('start', 0.0):.0f} s {f.get('worker', '?')}"
                  for f in per_run]
               + [f"`{name}` {s:.0f}"])
        print("| " + " | ".join(row) + " |")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
