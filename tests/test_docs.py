"""The documentation surface is part of tier-1: links must resolve, the
README quickstart must execute, and DESIGN.md's engine accounting must
match the method registry (the drift this PR's issue was filed about)."""
import pathlib
import re
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_doc_links_resolve():
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_docs.py"), "links"],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_readme_quickstart_executes():
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_docs.py"),
         "quickstart"],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_design_engine_table_matches_registry():
    """DESIGN.md §1 lists every HYPE engine the registry exposes, and its
    prose counts them consistently (no 'three engines' next to a
    five-row table again)."""
    text = (REPO / "DESIGN.md").read_text()
    sec1 = text.split("## 2.")[0]
    from repro.core.partition_api import METHODS
    for m in METHODS:
        if m.startswith("hype") and m not in ("hype_weighted",):
            assert f"`{m}`" in sec1, f"engine {m} missing from DESIGN §1"
    assert "three engines" not in text
    # eight ladder rows: five growth rungs (hype_jax is the side-rung),
    # the multilevel composition of the refinement subsystem (§4e), the
    # streaming/online engine (§4h) and the device-resident loop (§4i)
    table_rows = re.findall(r"^\| `hype", sec1, re.MULTILINE)
    assert len(table_rows) == 8


def test_readme_documents_the_commands():
    text = (REPO / "README.md").read_text()
    assert "python -m pytest" in text                  # tier-1
    assert "benchmarks.bench_engine_scaling" in text   # bench repro
    assert "BENCH_engines.json" in text
    assert "xla_force_host_platform_device_count" in text


def test_bare_names_resolve_in_the_source_trees_only():
    """A bare file name in the docs resolves by a walk of the source
    trees, never of the whole checkout, where test runs create and
    delete directories while the walk runs."""
    sys.path.insert(0, str(REPO / "tools"))
    import check_docs

    assert check_docs.in_source_tree("quickstart.py")      # examples/
    assert not check_docs.in_source_tree("conftest.py")    # tests/
    assert not check_docs.in_source_tree("no_such_module.py")
