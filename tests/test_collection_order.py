"""The longest-first ordering of tests/conftest.py, on a made-up collection."""

import os
import re
from types import SimpleNamespace

from conftest import _LONGEST_FIRST, _longest_first


def _items(*nodeids):
    return [SimpleNamespace(nodeid=n) for n in nodeids]


def test_named_files_come_first_and_the_rest_keep_their_order():
    names = ("test_c.py", "test_a.py")
    items = _items(
        "tests/ef/test_x.py::test_1",
        "tests/test_a.py::test_1",
        "tests/test_b.py::test_1",
        "tests/test_a.py::test_2[p-q]",
        "tests/test_c.py::test_2",
        "tests/test_b.py::test_0",
        "tests/test_c.py::test_1",
        "tests/sub/test_c.py::test_9",   # matched by file name, wherever it lives
    )
    got = [it.nodeid for it in _longest_first(items, names)]
    assert got == [
        # the named files, in the tuple's order; inside a file, as collected
        "tests/test_c.py::test_2",
        "tests/test_c.py::test_1",
        "tests/sub/test_c.py::test_9",
        "tests/test_a.py::test_1",
        "tests/test_a.py::test_2[p-q]",
        # every other item, in its collected order
        "tests/ef/test_x.py::test_1",
        "tests/test_b.py::test_1",
        "tests/test_b.py::test_0",
    ]
    # nothing dropped, nothing doubled
    assert sorted(got) == sorted(it.nodeid for it in items)
    assert len(set(got)) == len(items)


def test_the_tuple_names_files_that_exist(request):
    """A renamed or deleted file must not linger in the tuple, and the
    hook must hold for the collection it really ordered: this very run."""
    here = os.path.dirname(__file__)
    found = {
        f for _root, _dirs, files in os.walk(here) for f in files
    }
    assert len(set(_LONGEST_FIRST)) == len(_LONGEST_FIRST)
    assert [n for n in _LONGEST_FIRST if n not in found] == []
    # xdist would otherwise hand files out by their number of tests
    assert getattr(request.config.option, "loadscopereorder", False) is False
    collected = request.session.items
    assert [it.nodeid for it in _longest_first(collected)] == [
        it.nodeid for it in collected
    ]


#: every test file whose source reaches for the staged backend's programs,
#: and what it compiles of them in tier-1 (tests/README.md "Which module
#: compiles which program" prices them). A file that joins this list adds a
#: build to tier-1: say in CHANGES.md what it costs in worker-seconds
#: against the margin ROADMAP.md D1 states.
_FILES_THAT_REACH_THE_STAGES = {
    "test_jaxbls_backend.py": "two builds: 8 sets over the mesh, 4 on one chip",
    "test_jaxbls_registry.py": "nine one-device programs, stage 2 from the host",
    "test_kzg.py": "the KZG lane pass, the 4-pair Miller loop, final exponentiation",
    "test_jaxbls_msm.py": "the MSM kernels",
    "test_jaxbls_message_fold.py": "nothing: stubbed stages",
    "test_mesh.py": "nothing: stand-ins",
}


def _test_sources():
    here = os.path.dirname(__file__)
    for root, _dirs, files in os.walk(here):
        for f in files:
            if f.startswith("test_") and f.endswith(".py"):
                with open(os.path.join(root, f)) as src:
                    yield f, src.read()


def test_no_file_builds_the_staged_backend_unseen():
    """A new file cannot add a build of the stage programs to tier-1
    unseen: the files that call for the jax backend, the stages or a
    warm-up build are the ones listed above."""
    reaches = re.compile(
        r'set_backend\("jax"\)|_get_stages|_get_one_chip_variant|warm_build')
    found = {f for f, source in _test_sources()
             if reaches.search(source) and f != os.path.basename(__file__)}
    assert found == set(_FILES_THAT_REACH_THE_STAGES)


def test_the_readmes_program_table_names_modules_that_exist():
    """tests/README.md "Which module compiles which program": every module
    a row names is a test file, and every file listed above that compiles
    anything has a row."""
    with open(os.path.join(os.path.dirname(__file__), "README.md")) as f:
        readme = f.read()
    table = readme.split("## Which module compiles which program", 1)[1]
    table = table.split("\n## ", 1)[0]
    rows = [line for line in table.splitlines()
            if line.startswith("|") and "test_" in line]
    assert len(rows) >= 15
    named = {m for row in rows for m in re.findall(r"test_\w+\.py", row)}
    existing = {f for f, _source in _test_sources()}
    assert named <= existing, named - existing
    assert {f for f, what in _FILES_THAT_REACH_THE_STAGES.items()
            if not what.startswith("nothing")} <= named
