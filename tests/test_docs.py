"""docs/CONFIG.md is generated from the live dataclasses — regenerate
and diff so a config change can't silently leave the doc stale.
docs/DESIGN.md's layer-map module list is checked against the real tree
so a moved/renamed module can't silently orphan the architecture doc.
``cli.py``'s usage block and every subcommand's ``--help`` are held to
the parser, so a subcommand cannot be half removed."""

import argparse
import os
import re

import pytest

from colearn_federated_learning_tpu import cli
from colearn_federated_learning_tpu.utils.docgen import config_reference_markdown

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_design_doc_modules_exist():
    """Every `module.py` / `dir/` path named in DESIGN.md's layer table
    must exist under the package (README links the doc; a stale module
    list would send a newcomer to files that aren't there)."""
    with open(os.path.join(_ROOT, "docs", "DESIGN.md")) as f:
        text = f.read()
    # backticked paths inside the layer table, e.g. `server/round_driver.py`
    paths = set(re.findall(r"`([\w/]+\.(?:py|cpp))`", text))
    assert len(paths) >= 15, sorted(paths)  # the table really was parsed
    pkg = os.path.join(_ROOT, "colearn_federated_learning_tpu")
    missing = []
    for rel in sorted(paths):
        if not (
            os.path.exists(os.path.join(pkg, rel))      # package module
            or os.path.exists(os.path.join(_ROOT, rel))  # repo-level path
        ):
            missing.append(rel)
    assert not missing, f"DESIGN.md names modules that don't exist: {missing}"


def test_config_reference_is_current():
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "CONFIG.md",
    )
    with open(path) as f:
        committed = f.read()
    assert committed == config_reference_markdown(), (
        "docs/CONFIG.md is stale — regenerate with:\n"
        "  python -c \"from colearn_federated_learning_tpu.utils.docgen "
        "import config_reference_markdown; "
        "open('docs/CONFIG.md','w').write(config_reference_markdown())\""
    )


SUBCOMMANDS = ("fit", "evaluate", "export", "configs", "store", "summarize",
               "clients", "watch", "population", "check", "diff", "replay",
               "preflight")


def _subparsers(parser):
    return next(a for a in parser._actions
                if isinstance(a, argparse._SubParsersAction)).choices


def test_cli_usage_block_lists_the_parsers_subcommands():
    """The module docstring's ``colearn <sub>`` lines, the parser and
    the list the help test runs over name the same subcommands."""
    named = re.findall(r"^    colearn ([\w-]+)", cli.__doc__, re.M)
    in_parser = _subparsers(cli.build_parser())
    assert sorted(set(named)) == sorted(in_parser) == sorted(SUBCOMMANDS)
    assert "build|info" in cli.__doc__
    assert sorted(_subparsers(in_parser["store"])) == ["build", "info"]


@pytest.mark.parametrize("sub", [*SUBCOMMANDS, "store build", "store info"])
def test_cli_subcommand_help(sub, capsys):
    with pytest.raises(SystemExit) as done:
        cli.main([*sub.split(), "--help"])
    assert done.value.code == 0
    assert f"usage: colearn {sub}" in capsys.readouterr().out
