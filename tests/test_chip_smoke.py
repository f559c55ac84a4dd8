"""What lets the chip check start clean: a compile cache that can be
placed from outside, and a ``chip_smoke.py`` that fails where there is
no chip instead of passing on the CPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS")}
    env["PYTHONPATH"] = REPO
    env.update(extra)
    return env


_CACHE_SNIPPET = (
    "import json, jax, makisu_tpu.ops;"
    "print(json.dumps([jax.config.jax_compilation_cache_dir,"
    " jax.config.jax_persistent_cache_min_compile_time_secs]))")


def _cache_config(env, cwd):
    out = subprocess.run([sys.executable, "-c", _CACHE_SNIPPET], env=env,
                         cwd=cwd, capture_output=True, text=True,
                         timeout=120, check=True).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_compile_cache_defaults_to_the_checkout(tmp_path):
    """Unset: <checkout>/.jax_cache whatever the working directory —
    never a temporary name, a pid or a time — and every program is
    cached."""
    assert _cache_config(_env(), str(tmp_path)) \
        == [os.path.join(REPO, ".jax_cache"), 0.0]


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path):
    """Set: the program sets no directory and touches no threshold."""
    placed = str(tmp_path / "placed")
    assert _cache_config(
        _env(JAX_COMPILATION_CACHE_DIR=placed,
             JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="2.5"),
        str(tmp_path)) == [placed, 2.5]


def test_no_other_site_sets_a_cache_directory():
    """One site: nothing else in the program, the bench or the tests
    names a compile-cache directory."""
    hits = []
    for top in ("makisu_tpu", "benchmarks", "tests"):
        for root, _, names in os.walk(os.path.join(REPO, top)):
            hits += [os.path.join(root, n) for n in names
                     if n.endswith(".py")]
    hits += [os.path.join(REPO, n)
             for n in ("bench.py", "chip_smoke.py", "__graft_entry__.py")]
    setters = []
    for path in hits:
        with open(path, encoding="utf-8") as f:
            text = f.read()
        if ("jax_compilation_cache_dir\"," in text
                or "JAX_COMPILATION_CACHE_DIR\"]" in text
                or "setdefault(\"JAX_COMPILATION_CACHE_DIR" in text):
            setters.append(os.path.relpath(path, REPO))
    assert setters == [os.path.join("makisu_tpu", "ops", "__init__.py")]


def _smoke(cwd, script, **env):
    return subprocess.run([sys.executable, script], cwd=cwd,
                          env=_env(**env), capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_without_a_chip_fails_before_building_anything():
    done = _smoke(REPO, os.path.join(REPO, "chip_smoke.py"),
                  JAX_PLATFORMS="cpu")
    assert done.returncode != 0
    assert "JAX found no TPU" in done.stderr
    assert '"ok"' not in done.stdout          # no result line
    assert "== identity" in done.stdout
    for later in ("== native", "== context", "== cold_build"):
        assert later not in done.stdout


def test_chip_smoke_outside_a_checkout_fails(tmp_path):
    script = shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    done = _smoke(str(tmp_path), script)
    assert done.returncode != 0
    assert done.stdout == ""


def test_chip_smoke_parent_stays_off_jax():
    """One process for each chip: the parent imports no JAX at module
    level or anywhere outside the snippets it hands to children."""
    with open(os.path.join(REPO, "chip_smoke.py"), encoding="utf-8") as f:
        text = f.read()
    import ast
    imported = set()
    for node in ast.walk(ast.parse(text)):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert not imported & {"jax", "jaxlib", "makisu_tpu"}
    assert 'assert "jax" not in sys.modules' in text


def _import_chip_smoke():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    return chip_smoke


def test_chip_smoke_result_line_has_the_contract_keys_and_no_others():
    """The chip check reads the last line of standard output and refuses
    any key beyond these; what the run observed goes on the line
    before."""
    chip_smoke = _import_chip_smoke()
    line = chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
         "jax": "0.9.0", "default_backend": "tpu"})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    with open(os.path.join(REPO, "chip_smoke.py"), encoding="utf-8") as f:
        text = f.read()
    # It is the last thing main() writes, and the only bare print to
    # standard output besides say().
    assert text.count("print(result_line(ident)") == 1
    assert text.rindex("print(result_line(ident)") > text.rindex("say(")


@pytest.mark.parametrize("option", ["MAKISU_TPU_PALLAS",
                                    "MAKISU_TPU_CHUNK_NATIVE",
                                    "MAKISU_TPU_SHARED_HASH",
                                    "MAKISU_TPU_CHUNK_STRICT"])
def test_chip_smoke_children_inherit_no_route_option(monkeypatch, option):
    chip_smoke = _import_chip_smoke()
    monkeypatch.setenv(option, "1")
    monkeypatch.setenv("MAKISU_TPU_SYNC_TIMEOUT", "30")  # not a route
    env = chip_smoke.child_env()
    assert option not in env
    assert env["MAKISU_TPU_SYNC_TIMEOUT"] == "30"
