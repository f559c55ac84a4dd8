import pytest

from makisu_tpu.utils import pathutils as pu


def test_abs_rel():
    assert pu.abs_path("a/b") == "/a/b"
    assert pu.abs_path("/a//b/../c") == "/a/c"
    assert pu.rel_path("/a/b") == "a/b"


def test_trim_join_root():
    assert pu.trim_root("/root/x/a/b", "/root/x") == "/a/b"
    assert pu.trim_root("/root/x", "/root/x") == "/"
    assert pu.join_root("/sandbox", "/a/b") == "/sandbox/a/b"
    with pytest.raises(ValueError):
        pu.trim_root("/other/a", "/root/x")


def test_descendants_and_ancestors():
    assert pu.is_descendant_of_any("/proc/1", ["/proc", "/sys"])
    assert pu.is_descendant_of_any("/proc", ["/proc"])
    assert not pu.is_descendant_of_any("/procx", ["/proc"])
    assert pu.ancestors("/a/b/c") == ["/a", "/a/b"]
    assert pu.ancestors("/a") == []


# -- a request's directories, resolved once (PR 45) --------------------------


@pytest.fixture
def request_dirs(tmp_path):
    """--storage is a link to a directory, as a request's admission
    would have resolved and bound it."""
    target = tmp_path / "target"
    target.mkdir()
    link = tmp_path / "storage"
    link.symlink_to(target)
    known = pu.resolve_request_dirs([str(link)])
    token = pu.bind_request_dirs(known)
    yield link, target, known
    pu.reset_request_dirs(token)


def _walked(monkeypatch):
    import os
    walks = []
    realpath = os.path.realpath
    monkeypatch.setattr(os.path, "realpath",
                        lambda p: walks.append(p) or realpath(p))
    return walks


def test_real_path_outside_a_request_is_realpath(tmp_path, monkeypatch):
    (tmp_path / "d").mkdir()
    (tmp_path / "l").symlink_to(tmp_path / "d")
    walks = _walked(monkeypatch)
    assert pu.real_path(str(tmp_path / "l")) == str(tmp_path / "d")
    assert pu.real_path("rel/../x") == str(tmp_path.cwd() / "x")
    assert len(walks) == 2


def test_real_path_answers_for_the_requests_directories(
        request_dirs, monkeypatch):
    link, target, known = request_dirs
    walks = _walked(monkeypatch)
    for form in (str(link), str(target)):
        assert pu.real_path(form) == str(target)
    assert walks == []
    # Another path is walked, and not remembered.
    other = str(target.parent / "other")
    assert pu.real_path(other) == other
    assert walks == [other] and other not in known


def test_real_path_below_a_known_directory_costs_one_component(
        request_dirs, monkeypatch):
    link, target, known = request_dirs
    (target / "chunks").mkdir()
    (target / "elsewhere").mkdir()
    (target / "layers").symlink_to(target / "elsewhere")
    walks = _walked(monkeypatch)
    # A plain child: one lstat, no walk, known from then on.
    assert pu.real_path(str(link / "chunks")) == str(target / "chunks")
    assert pu.real_path(str(link / "chunks")) == str(target / "chunks")
    assert pu.real_path(str(target / "chunks")) == str(target / "chunks")
    assert walks == []
    # A child that is itself a link is walked from its real parent.
    assert pu.real_path(str(link / "layers")) == str(target / "elsewhere")
    assert walks == [str(target / "layers")]
    # Two components below what the request knows is not its to answer;
    # one below what it has learned since is.
    deep = str(link / "packs" / "ab")
    assert pu.real_path(deep) == str(target / "packs" / "ab")
    assert walks[-1] == deep and len(walks) == 2
    assert pu.real_path(str(link / "chunks" / "ab")) == \
        str(target / "chunks" / "ab")
    assert len(walks) == 2


def test_the_requests_directories_are_gone_with_the_binding(tmp_path):
    import contextvars
    known = pu.resolve_request_dirs([str(tmp_path)])

    def inside():
        pu.bind_request_dirs(known)
        return pu._request_dirs.get()

    assert contextvars.copy_context().run(inside) is known
    assert pu._request_dirs.get() is None
