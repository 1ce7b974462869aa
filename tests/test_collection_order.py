"""The longest-first ordering of tests/conftest.py, on a made-up collection."""

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
    import os

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
