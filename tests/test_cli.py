"""CLI end-to-end: build a context through the real entry point."""

import io
import json
import subprocess
import sys
import tarfile

import pytest

from makisu_tpu import cli


@pytest.fixture
def context(tmp_path):
    ctx = tmp_path / "ctx"
    ctx.mkdir()
    (ctx / "Dockerfile").write_text(
        "FROM scratch\n"
        "COPY greeting.txt /etc/greeting\n"
        'ENTRYPOINT ["/bin/app"]\n')
    (ctx / "greeting.txt").write_text("hello from makisu-tpu\n")
    return ctx


def test_version():
    assert cli.main(["version"]) == 0


def test_build_to_dest(tmp_path, context):
    root = tmp_path / "root"
    root.mkdir()
    dest = tmp_path / "image.tar"
    rc = cli.main([
        "--log-fmt", "console", "build", str(context),
        "-t", "demo/app:latest",
        "--storage", str(tmp_path / "storage"),
        "--root", str(root),
        "--dest", str(dest),
    ])
    assert rc == 0
    with tarfile.open(dest) as tf:
        names = tf.getnames()
        export = json.load(tf.extractfile("manifest.json"))
    assert export[0]["RepoTags"] == ["demo/app:latest"]
    assert any(n.endswith("layer.tar") for n in names)
    # The layer holds the copied file.
    with tarfile.open(dest) as tf:
        layer_name = export[0]["Layers"][0]
        inner = tarfile.open(fileobj=io.BytesIO(
            tf.extractfile(layer_name).read()))
        members = {m.name for m in inner}
    assert "etc/greeting" in members


def test_build_missing_dockerfile_fails(tmp_path):
    empty = tmp_path / "empty"
    empty.mkdir()
    rc = cli.main(["build", str(empty), "-t", "x:y",
                   "--storage", str(tmp_path / "s"),
                   "--root", str(tmp_path / "r")])
    assert rc == 1


def test_cli_subprocess_entrypoint(tmp_path, context):
    """The module runs as python -m makisu_tpu.cli (console-script path)."""
    out = subprocess.run(
        [sys.executable, "-m", "makisu_tpu.cli", "version"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0
    assert out.stdout.strip()


def _with_fixture_registry(images):
    """Route CLI registry traffic to an in-process fixture serving
    {(repo, tag): files_dict}."""
    from makisu_tpu.registry import RegistryFixture, make_test_image
    from makisu_tpu.registry import client as client_mod
    fixture = RegistryFixture()
    for (repo, tag), files in images.items():
        manifest, _, blobs = make_test_image(files)
        fixture.serve_image(repo, tag, manifest, blobs)
    client_mod.set_transport_factory(lambda name: fixture)
    return fixture


@pytest.fixture
def fixture_registry():
    yield _with_fixture_registry
    from makisu_tpu.registry import client as client_mod
    client_mod.set_transport_factory(None)


def test_cli_pull_extract(tmp_path, fixture_registry):
    fixture_registry({("library/busy", "v1"): {"bin/sh": b"#!"}})
    dest = tmp_path / "rootfs"
    rc = cli.main(["pull", "busy:v1", "--extract", str(dest),
                   "--storage", str(tmp_path / "s")])
    assert rc == 0
    assert (dest / "bin" / "sh").read_bytes() == b"#!"


def test_cli_diff(tmp_path, fixture_registry, capsys):
    fixture_registry({
        ("library/imga", "latest"): {"common": b"same", "only-a": b"a"},
        ("library/imgb", "latest"): {"common": b"same", "only-b": b"bb"},
    })
    rc = cli.main(["diff", "imga", "imgb",
                   "--storage", str(tmp_path / "s")])
    assert rc == 0
    out = capsys.readouterr().out
    assert "only-a" in out and "only-b" in out
    assert "common" not in out


def test_cli_diff_whole_config(tmp_path, fixture_registry, capsys):
    """diff must report differences outside config.* — the reference
    go-cmp's the entire image config (cmd/diff.go:117-120)."""
    import json

    from makisu_tpu.docker.image import (
        MEDIA_TYPE_CONFIG,
        Descriptor,
        Digest,
        DistributionManifest,
    )
    from makisu_tpu.registry import make_test_image

    fixture = fixture_registry(
        {("library/imga", "latest"): {"f": b"same"}})
    manifest, config_blob, blobs = make_test_image({"f": b"same"})
    cfg = json.loads(config_blob)
    cfg["architecture"] = "arm64"  # identical except architecture
    new_blob = json.dumps(cfg).encode()
    new_digest = Digest.of_bytes(new_blob)
    manifest_b = DistributionManifest(
        config=Descriptor(MEDIA_TYPE_CONFIG, len(new_blob), new_digest),
        layers=manifest.layers)
    blobs_b = dict(blobs)
    del blobs_b[manifest.config.digest.hex()]
    blobs_b[new_digest.hex()] = new_blob
    fixture.serve_image("library/imgb", "latest", manifest_b, blobs_b)

    rc = cli.main(["diff", "imga", "imgb",
                   "--storage", str(tmp_path / "s")])
    assert rc == 0
    out = capsys.readouterr().out
    assert "architecture" in out and "arm64" in out


def test_cli_push_tar(tmp_path, fixture_registry, context):
    fixture = fixture_registry({})
    root = tmp_path / "root"
    root.mkdir()
    dest = tmp_path / "image.tar"
    assert cli.main(["build", str(context), "-t", "team/pushme:1",
                     "--storage", str(tmp_path / "s1"),
                     "--root", str(root), "--dest", str(dest)]) == 0
    rc = cli.main(["push", str(dest), "-t", "team/pushme:1",
                   "--push", "registry.test",
                   "--storage", str(tmp_path / "s2")])
    assert rc == 0
    assert "team/pushme:1" in fixture.manifests


def test_cli_build_push(tmp_path, fixture_registry, context):
    fixture = fixture_registry({})
    root = tmp_path / "root"
    root.mkdir()
    rc = cli.main(["build", str(context), "-t", "team/direct:2",
                   "--storage", str(tmp_path / "s"),
                   "--root", str(root),
                   "--push", "registry.test"])
    assert rc == 0
    assert "team/direct:2" in fixture.manifests


def test_cli_build_replicas(tmp_path, fixture_registry, context):
    fixture = fixture_registry({})
    root = tmp_path / "root"
    root.mkdir()
    rc = cli.main(["build", str(context), "-t", "team/app:main",
                   "--replica", "team/app:canary",
                   "--storage", str(tmp_path / "s"),
                   "--root", str(root),
                   "--push", "registry.test"])
    assert rc == 0
    assert "team/app:main" in fixture.manifests
    assert "team/app:canary" in fixture.manifests
    assert fixture.manifests["team/app:main"] == \
        fixture.manifests["team/app:canary"]


@pytest.mark.parametrize("level", ["no", "speed", "size"])
def test_build_compression_levels(tmp_path, context, level):
    # Compression is per-build (threaded through BuildContext, never the
    # tario process globals), so no cross-test restore is needed.
    root = tmp_path / f"root-{level}"
    root.mkdir()
    dest = tmp_path / f"img-{level}.tar"
    rc = cli.main(["build", str(context), "-t", f"c/{level}:1",
                   "--storage", str(tmp_path / f"s-{level}"),
                   "--root", str(root), "--compression", level,
                   "--dest", str(dest)])
    assert rc == 0
    assert dest.exists()


def test_jax_profile_flag_writes_trace(tmp_path, context):
    """--jax-profile brackets the command with a profiler trace and
    leaves its files in the directory given."""
    root = tmp_path / "root"
    root.mkdir()
    trace = tmp_path / "trace"
    rc = cli.main(["--jax-profile", str(trace),
                   "build", str(context), "-t", "prof/t:1",
                   "--storage", str(tmp_path / "s"), "--root", str(root)])
    assert rc == 0
    files = [p for p in trace.rglob("*") if p.is_file()]
    assert files  # xplane/trace artifacts written


# -- one parser a process (PR 45) --------------------------------------------


def test_parse_args_builds_its_tree_once(monkeypatch):
    built = []
    tree = cli._parser_tree
    monkeypatch.setattr(cli, "_parser_tree",
                        lambda: built.append(1) or tree())
    monkeypatch.setattr(cli, "_shared_tree", None)
    for tag in ("a:1", "b:2", "c:3"):
        assert cli.parse_args(["build", "ctx", "-t", tag]).tag == tag
    assert built == [1]
    # make_parser still hands out a tree of one's own.
    assert cli.make_parser() is not cli.make_parser()
    assert len(built) == 3


def test_parse_args_reads_the_environments_defaults_each_parse(
        monkeypatch):
    """DOCKER_HOST and DOCKER_VERSION are defaults read from the
    environment: the kept tree must not hand out the values of the
    parse that built it."""
    argv = ["build", "ctx", "-t", "e:1"]
    monkeypatch.delenv("DOCKER_HOST", raising=False)
    monkeypatch.delenv("DOCKER_VERSION", raising=False)
    args = cli.parse_args(argv)
    assert (args.docker_host, args.docker_version) == (
        "unix:///var/run/docker.sock", "1.21")
    monkeypatch.setenv("DOCKER_HOST", "tcp://10.0.0.7:2375")
    monkeypatch.setenv("DOCKER_VERSION", "1.40")
    args = cli.parse_args(argv)
    assert (args.docker_host, args.docker_version) == (
        "tcp://10.0.0.7:2375", "1.40")
    # A flag still wins over the environment.
    args = cli.parse_args(argv + ["--docker-host", "unix:///x.sock"])
    assert (args.docker_host, args.docker_version) == (
        "unix:///x.sock", "1.40")
    fresh = cli.make_parser().parse_args(argv)
    assert fresh.docker_host == "tcp://10.0.0.7:2375"
    monkeypatch.delenv("DOCKER_HOST")
    assert cli.parse_args(argv).docker_host == \
        "unix:///var/run/docker.sock"


def test_sixteen_threads_parse_their_own_flags():
    """One parser, sixteen threads, different ``argv``s, a few hundred
    parses each: every namespace is its own thread's."""
    import threading
    wrong = []
    start = threading.Barrier(16)

    def one(i):
        argv = ["--log-level", ("debug", "info", "warn", "error")[i % 4],
                "--hash-workers", str(i), "build", f"/ctx/{i}",
                "-t", f"t/{i}:1", f"--storage=/s/{i}", "--roo", f"/r/{i}",
                "--build-arg", f"N={i}", "--blacklist", f"/b/{i}"]
        if i % 2:
            argv += ["--modifyfs", "--commit", "explicit"]
        start.wait()
        for _ in range(200):
            args = cli.parse_args(argv)
            got = (args.log_level, args.hash_workers, args.command,
                   args.context, args.tag, args.storage, args.root,
                   args.build_arg, args.blacklist, args.modifyfs,
                   args.commit)
            want = (("debug", "info", "warn", "error")[i % 4], i, "build",
                    f"/ctx/{i}", f"t/{i}:1", f"/s/{i}", f"/r/{i}",
                    [f"N={i}"], [f"/b/{i}"], bool(i % 2),
                    "explicit" if i % 2 else "implicit")
            if got != want:
                wrong.append((i, got))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(16)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)     # hand the interpreter on mid-parse
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert wrong == []


def test_main_takes_a_parsed_namespace(tmp_path, context, monkeypatch):
    """Handed ``args``, ``main`` parses nothing: ``argv`` is not even
    looked at."""
    dest = tmp_path / "parsed.tar"
    args = cli.parse_args([
        "build", str(context), "-t", "test/parsed:1",
        "--storage", str(tmp_path / "storage"),
        "--root", str(tmp_path / "root"), "--dest", str(dest)])
    (tmp_path / "root").mkdir()
    monkeypatch.setattr(cli, "parse_args", None)
    assert cli.main(["no", "such", "command"], args) == 0
    assert dest.exists()


@pytest.mark.parametrize("argv, code, message", [
    (["build", "ctx"], 2, "the following arguments are required: -t"),
    (["build", "ctx", "-t", "x:1", "--hasher", "gpu"], 2,
     "invalid choice: 'gpu'"),
    (["frobnicate"], 2, "invalid choice: 'frobnicate'"),
])
def test_malformed_argv_exits_with_argparses_message(capsys, argv, code,
                                                     message):
    with pytest.raises(SystemExit) as raised:
        cli.main(argv)
    assert raised.value.code == code
    assert message in capsys.readouterr().err
