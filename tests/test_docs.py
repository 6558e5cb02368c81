"""Docs stay honest: intra-repo links resolve, pydoc renders cleanly.

Two cheap tier-1 guards backing the CI ``docs`` job:

* every ``[text](target)`` markdown link in ``docs/`` and the root
  ``*.md`` files points at a file that exists (``tools/check_docs_links``
  is the shared implementation, so CI and tier-1 cannot drift);
* ``pydoc`` renders every ``repro.fleetsim`` module without error, each
  module carries a docstring, and the public API of the sweep-facing
  modules (``stages``, ``shard``, ``sweep``) is fully docstringed — the
  "pydoc-clean" bar for the documented architecture.
"""

import importlib
import importlib.util
import inspect
import pydoc
from pathlib import Path

import pytest

ROOT = Path(__file__).parent.parent

FLEETSIM_MODULES = [
    "repro.fleetsim",
    "repro.fleetsim.config",
    "repro.fleetsim.engine",
    "repro.fleetsim.llmserve",
    "repro.fleetsim.llmserve.oracle",
    "repro.fleetsim.llmserve.service",
    "repro.fleetsim.llmserve.stage",
    "repro.fleetsim.metrics",
    "repro.fleetsim.policies",
    "repro.fleetsim.shard",
    "repro.fleetsim.spans",
    "repro.fleetsim.stages",
    "repro.fleetsim.state",
    "repro.fleetsim.sweep",
    "repro.fleetsim.validate",
]


def _load_linkcheck():
    spec = importlib.util.spec_from_file_location(
        "check_docs_links", ROOT / "tools" / "check_docs_links.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_docs_exist_and_are_linked():
    assert (ROOT / "docs" / "architecture.md").is_file()
    assert (ROOT / "docs" / "scenarios.md").is_file()
    readme = (ROOT / "README.md").read_text()
    assert "docs/architecture.md" in readme
    assert "docs/scenarios.md" in readme


def test_intra_repo_markdown_links_resolve():
    lc = _load_linkcheck()
    errors = [e for f in lc.md_files(ROOT) for e in lc.check_file(f, ROOT)]
    assert not errors, "\n".join(errors)


def test_linkchecker_catches_breakage(tmp_path):
    """The guard itself must fail on a genuinely broken link (and ignore
    code blocks, external URLs, and in-page anchors)."""
    lc = _load_linkcheck()
    md = tmp_path / "doc.md"
    md.write_text("ok [a](https://x.example) [b](#anchor)\n"
                  "`[c](nope.md)` and\n```\n[d](also-nope.md)\n```\n"
                  "[real](missing.md)\n")
    errors = lc.check_file(md, tmp_path)
    assert len(errors) == 1 and "missing.md" in errors[0]


@pytest.mark.parametrize("modname", FLEETSIM_MODULES)
def test_pydoc_renders_fleetsim_module(modname):
    pytest.importorskip("jax")
    mod = importlib.import_module(modname)
    assert inspect.getdoc(mod), f"{modname} has no module docstring"
    text = pydoc.render_doc(mod)   # raises if the module can't be rendered
    assert modname.rsplit(".", 1)[-1] in text


@pytest.mark.parametrize("modname", ["repro.fleetsim.stages",
                                     "repro.fleetsim.shard",
                                     "repro.fleetsim.spans",
                                     "repro.fleetsim.sweep"])
def test_public_api_is_docstringed(modname):
    pytest.importorskip("jax")
    mod = importlib.import_module(modname)
    missing = []
    for name, obj in vars(mod).items():
        if name.startswith("_"):
            continue
        if not (inspect.isfunction(obj) or inspect.isclass(obj)):
            continue
        if getattr(obj, "__module__", None) != modname:
            continue  # re-exports document themselves at home
        if not inspect.getdoc(obj):
            missing.append(name)
    assert not missing, f"{modname}: undocumented public API {missing}"
