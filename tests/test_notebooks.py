"""The notebook demo surface: freshness + real-kernel execution.

The reference ships runnable sample notebooks and executes them in CI
(reference: notebooks/samples/, tools/notebook/tester/
NotebookTestSuite.py:13-60, TestNotebooksLocally.py:9-29). Here the
notebooks are derived from ``examples/*.py`` by
``mmlspark_tpu.tools.make_notebooks``:

* the freshness test (default lane) regenerates the set and fails if the
  committed ``notebooks/samples/`` drifted from the examples,
* the execution tests (slow lane) run every notebook through a REAL
  jupyter kernel via nbclient — the demo artifact a user opens in the
  Docker image's jupyter entry must actually run.
"""

import glob
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NB_DIR = os.path.join(REPO, "notebooks", "samples")


def committed_notebooks() -> list[str]:
    return sorted(glob.glob(os.path.join(NB_DIR, "*.ipynb")))


def test_notebooks_fresh(tmp_path):
    """notebooks/samples/ must be regenerable byte-stable from examples/
    (same freshness contract as the generated API docs)."""
    import nbformat

    from mmlspark_tpu.tools.make_notebooks import build

    regen = build(str(tmp_path))
    committed = committed_notebooks()
    assert len(committed) == len(regen) == 11, (
        f"expected 11 notebooks, committed={len(committed)} "
        f"regenerated={len(regen)} — run python -m "
        "mmlspark_tpu.tools.make_notebooks")
    for new_path in regen:
        old_path = os.path.join(NB_DIR, os.path.basename(new_path))
        assert os.path.exists(old_path), f"missing committed {old_path}"
        old = nbformat.read(old_path, as_version=4)
        new = nbformat.read(new_path, as_version=4)
        assert [c.source for c in old.cells] == \
            [c.source for c in new.cells], (
                f"{os.path.basename(old_path)} is stale — regenerate with "
                "python -m mmlspark_tpu.tools.make_notebooks")


@pytest.mark.slow
@pytest.mark.parametrize("nb_path", committed_notebooks(),
                         ids=[os.path.basename(p).split(" - ")[0]
                              for p in committed_notebooks()])
def test_notebook_executes(nb_path, tmp_path):
    """Every sample notebook runs top to bottom in a real kernel."""
    import nbformat
    from nbclient import NotebookClient

    nb = nbformat.read(nb_path, as_version=4)
    # test-only preamble (NOT in the committed notebook): pin the kernel
    # to the CPU backend (tier-1 never takes a chip, even on a machine
    # that has one) and put the repo on sys.path since the kernel cwd is
    # a scratch dir
    pin = nbformat.v4.new_code_cell(
        "import sys; sys.path.insert(0, %r)\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')" % REPO)
    nb.cells.insert(0, pin)
    client = NotebookClient(nb, timeout=600, kernel_name="python3",
                            resources={"metadata": {"path": str(tmp_path)}})
    client.execute()  # raises CellExecutionError on any failing cell
    # at least one cell produced output (the examples all print results)
    outs = [o for c in nb.cells if c.cell_type == "code"
            for o in c.get("outputs", [])]
    assert outs, "notebook executed but produced no output"
