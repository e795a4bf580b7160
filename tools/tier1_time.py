#!/usr/bin/env python3
"""Where tier-1's seconds go, from a run's junit file (``--junitxml``).

    python tools/tier1_time.py /tmp/_t1.xml [workers, 6] [files shown, 15]

Prints the test-seconds of each file (tests, sum, longest case), their even
split over the workers, and the wall that pytest-xdist's ``--dist loadfile``
gives for the queue in collection order (what ``tests/conftest.py`` asks for)
and in xdist's own order, by number of tests.  A file is one worker's, so
the longest file is the floor of the wall, and a file that starts late and
runs long is what ends the run: budget a new cell's tests with this.
"""

import collections
import sys
import xml.etree.ElementTree as ET


def files(junit):
    """``{file: [seconds of each of its cases, in the order they ran]}`` in
    collection order: pytest sorts a directory's entries by name.  A
    ``classname`` is the module's dotted path and then any ``Test*`` class."""
    found = collections.defaultdict(list)
    for case in ET.parse(junit).getroot().iter("testcase"):
        module = [part for part in case.get("classname").split(".")
                  if not part.startswith("Test")]
        found["/".join(module) + ".py"].append(float(case.get("time")))
    return dict(sorted(found.items(), key=lambda item: item[0].split("/")))


def replay(queue, workers=6):
    """(wall seconds, the file that ends the run, the second it started) of
    xdist's rule over ``queue``, ``[(file, [seconds])]``: a worker is handed
    the next file when two or fewer of its tests are pending."""
    queue = collections.deque(queue)
    pending = [collections.deque() for _ in range(workers)]
    clock = [0.0] * workers
    last = [("", 0.0)] * workers

    def hand(w, limit=2):
        if queue and len(pending[w]) <= limit:
            name, seconds = queue.popleft()
            last[w] = (name, clock[w] + sum(pending[w]))
            pending[w].extend(seconds)

    for limit in (0, 2):  # xdist's start: a file each, then the rule
        for w in range(workers):
            hand(w, limit)
    while any(pending):
        w = min((w for w in range(workers) if pending[w]),
                key=lambda w: clock[w] + pending[w][0])
        clock[w] += pending[w].popleft()
        hand(w)
    w = max(range(workers), key=clock.__getitem__)
    return (clock[w], *last[w])


def report(junit, workers=6, top=15):
    found = files(junit)
    total = sum(map(sum, found.values()))
    lines = [f"{sum(map(len, found.values()))} tests in {len(found)} files, "
             f"{total:.0f} test-seconds; an even split over {workers} "
             f"workers: {total / workers:.0f} s",
             f"{'seconds':>8} {'tests':>6} {'longest':>8}  file"]
    longest = sorted(found.items(), key=lambda item: -sum(item[1]))[:top]
    for name, seconds in longest:
        lines.append(f"{sum(seconds):8.0f} {len(seconds):6d} "
                     f"{max(seconds):8.0f}  {name}")
    by_count = sorted(found.items(), key=lambda item: -len(item[1]))
    queues = ("collection", found.items()), ("test count", by_count)
    for order, queue in queues:
        wall, name, start = replay(queue, workers)
        lines.append(f"queue in {order} order: wall {wall:.0f} s; "
                     f"{name} ends it (started at {start:.0f} s)")
    return "\n".join(lines)


if __name__ == "__main__":
    print(report(sys.argv[1], *map(int, sys.argv[2:4])))
