"""scripts/suite_schedule.py: the wall xdist's loadfile rule gives a junit
file, and the tests that sit where the rule makes them cost the most."""
import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "suite_schedule",
    pathlib.Path(__file__).parent.parent / "scripts" / "suite_schedule.py")
suite_schedule = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(suite_schedule)


def _case(module, name, seconds):
    return f'<testcase classname="{module}" name="{name}" time="{seconds}" />'


def test_three_files_on_two_workers_give_the_rule_s_wall_and_offenders(
        tmp_path, capsys):
    # Most tests first: test_many (8) and test_few (3) start the two workers.
    # test_few's worker has two tests left after its first (10 s), before
    # test_many's has (18 s), and takes test_pair: it ends at
    # 3 * 10 + 2 * 250 = 530 s, long after test_many (21 + 30 s), whose last
    # test is the one that breaks the rule.
    cases = [_case("tests.test_many", f"t{i}", 3) for i in range(7)]
    cases.append(_case("tests.test_many", "t_long[a-b]", 30))
    cases += [_case("tests.sub.test_few.TestClass", f"t{i}", 10)
              for i in range(3)]
    cases += [_case("tests.test_pair", f"t{i}", 250) for i in range(2)]
    junit = tmp_path / "junit.xml"
    junit.write_text('<?xml version="1.0"?><testsuites><testsuite>'
                     + "".join(cases) + "</testsuite></testsuites>")

    files = suite_schedule.read(junit)
    assert {f: len(t) for f, t in files.items()} == {
        "tests/test_many.py": 8, "tests/sub/test_few.py": 3,
        "tests/test_pair.py": 2}
    wall, ends = suite_schedule.schedule(files, 2)
    assert wall == 530.0
    assert ends == [("tests/sub/test_few.py", 30.0),
                    ("tests/test_many.py", 51.0),
                    ("tests/test_pair.py", 530.0)]
    # One worker runs everything in queue order; a third has nothing to take.
    assert suite_schedule.schedule(files, 1)[0] == 581.0
    assert suite_schedule.schedule(files, 3)[0] == 500.0
    assert suite_schedule.offenders(files) == (
        [("tests/test_many.py", "t_long[a-b]", 30.0)],
        [("tests/test_pair.py", 500.0)])

    suite_schedule.main([str(junit), "-n", "2"])
    out = capsys.readouterr().out
    assert "wall under -n 2 --dist loadfile: 530 s" in out
    assert "30.0 s  tests/test_many.py::t_long[a-b]" in out
    assert "500.0 s  tests/test_pair.py" in out
