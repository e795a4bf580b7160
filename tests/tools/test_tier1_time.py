"""``tools/tier1_time.py`` on a made-up junit file of a dozen cases on two
workers: a file of three long tests that collection puts first and xdist's
count order last, where it starts late and ends the run."""

from tools import tier1_time

# as a run writes it: in the order the cases finished, the workers' mixed
CASES = [("tests.b.test_many.TestThings", 10), ("tests.a.test_long", 40),
         ("tests.b.test_many.TestThings", 10), ("tests.b.test_many", 10),
         ("tests.b.test_many", 10), ("tests.a.test_long", 40),
         ("tests.b.test_many", 10), ("tests.test_mid", 10),
         ("tests.test_mid", 10), ("tests.test_mid", 10),
         ("tests.a.test_long", 40), ("tests.test_mid", 10)]


def test_the_queues_order_decides_the_wall(tmp_path):
    junit = tmp_path / "t1.xml"
    junit.write_text(
        '<?xml version="1.0"?><testsuites><testsuite name="pytest" tests="12">'
        + "".join(f'<testcase classname="{c}" name="test_{i}" time="{s}.000"/>'
                  for i, (c, s) in enumerate(CASES))
        + "</testsuite></testsuites>")
    found = tier1_time.files(junit)
    assert {k: (len(v), sum(v)) for k, v in found.items()} == {
        "tests/a/test_long.py": (3, 120), "tests/b/test_many.py": (5, 50),
        "tests/test_mid.py": (4, 40)}
    assert list(found) == ["tests/a/test_long.py", "tests/b/test_many.py",
                           "tests/test_mid.py"]
    # collection order: the long file starts at once, the second worker
    # takes the third file when two of its five tests are pending
    assert tier1_time.replay(found.items(), 2) == (
        120, "tests/a/test_long.py", 0)
    # count order: the long file waits for the worker that has the four
    by_count = sorted(found.items(), key=lambda item: -len(item[1]))
    assert tier1_time.replay(by_count, 2) == (160, "tests/a/test_long.py", 40)
    lines = tier1_time.report(junit, workers=2, top=2).splitlines()
    assert lines[0] == ("12 tests in 3 files, 210 test-seconds; an even split "
                        "over 2 workers: 105 s")
    assert lines[2].split() == ["120", "3", "40", "tests/a/test_long.py"]
    assert len(lines) == 6 and "wall 120 s" in lines[4]
    assert lines[5] == ("queue in test count order: wall 160 s; "
                        "tests/a/test_long.py ends it (started at 40 s)")
