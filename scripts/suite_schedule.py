"""Read a junit file; print what `pytest -n N --dist loadfile` makes of it.

xdist hands out whole files, ordered by their number of tests, most first (ties
keep collection order), and gives a worker its next file when it has two tests
or fewer left (xdist/scheduler/loadscope.py): the wall follows the place of the
long files. The run adds start-up and collection (24 s on PR 46's tree).
"""
import argparse
import xml.etree.ElementTree as ET

LONG_S, SMALL_FILE, SMALL_FILE_S = 25.0, 6, 450.0  # the rule in tests/conftest.py


def read(path):
    """{file: [(test, seconds)]}, a file's tests in the order they ran."""
    files = {}
    for case in ET.parse(path).iter("testcase"):
        parts = case.get("classname").split(".")
        last = max(i for i, p in enumerate(parts) if p.startswith("test_"))
        files.setdefault("/".join(parts[: last + 1]) + ".py", []).append(
            (case.get("name"), float(case.get("time"))))
    return files


def schedule(files, workers):
    """(wall, [(file, second it finished)]) under the scheduler's rule."""
    queue = sorted(sorted(files), key=lambda f: -len(files[f]))[::-1]
    todo = [[] for _ in range(workers)]  # per worker: (file, seconds) to run
    clock, ends = [0.0] * workers, {}

    def refill(w):
        if len(todo[w]) <= 2 and queue:
            name = queue.pop()
            todo[w] += [(name, s) for _, s in files[name]]

    for w in list(range(workers)) * 2:  # a second file at once only beside one of <= 2 tests
        refill(w)
    while any(todo):
        w = min((w for w in range(workers) if todo[w]), key=lambda w: clock[w] + todo[w][0][1])
        name, seconds = todo[w].pop(0)
        ends[name] = clock[w] = clock[w] + seconds
        refill(w)
    return max(clock), sorted(ends.items(), key=lambda kv: kv[1])


def offenders(files):
    """What breaks the rule: (long tests in large files, small files too long)."""
    seconds = {f: sum(s for _, s in tests) for f, tests in files.items()}
    return ([(f, t, s) for f, tests in files.items() if len(tests) > SMALL_FILE
             for t, s in tests if s > LONG_S],
            [(f, seconds[f]) for f, tests in files.items()
             if len(tests) <= SMALL_FILE and seconds[f] > SMALL_FILE_S])


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("junit")
    parser.add_argument("-n", type=int, default=6, dest="workers")
    args = parser.parse_args(argv)
    files, took = read(args.junit), float(next(ET.parse(args.junit).iter("testsuite")).get("time", 0))
    seconds = {f: sum(s for _, s in tests) for f, tests in files.items()}
    for name in sorted(files, key=lambda f: -seconds[f]):
        print(f"{seconds[name]:8.1f} s {len(files[name]):4d}  {name}")
    total = sum(seconds.values())
    outside = sum(s for f, s in seconds.items() if not f.startswith("tests/benchmark/"))
    wall, ends = schedule(files, args.workers)
    print(f"tests {sum(map(len, files.values()))}, test seconds {total:.0f} ({outside:.0f} outside tests/benchmark/)")
    print(f"wall under -n {args.workers} --dist loadfile: {wall:.0f} s (total/{args.workers} "
          f"{total / args.workers:.0f} s; the run itself, with start-up and collection: {took:.0f} s)")
    print(*(f"  finishes at {end:6.0f} s  {name}" for name, end in ends[-4:]), sep="\n")
    long_tests, long_files = offenders(files)
    for f, t, s in sorted(long_tests, key=lambda found: -found[2]):
        print(f"over {LONG_S:.0f} s in a file of more than {SMALL_FILE} tests: {s:6.1f} s  {f}::{t}")
    for f, s in long_files:
        print(f"file of {SMALL_FILE} tests or fewer over {SMALL_FILE_S:.0f} s: {s:6.1f} s  {f}")


if __name__ == "__main__":
    main()
