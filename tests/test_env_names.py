"""Every ``RAMBA_*`` name the package reads is documented, and every name
the documentation gives is read somewhere in the repository.

A name counts as read when it appears in the source (the regex below),
which is how the knob count in ``ROADMAP.md`` is taken.  The reverse
direction accepts ``scripts/``, ``benchmark/``, ``tests/`` and
``chip_smoke.py`` as readers too, since the docs describe test switches.
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NAME = re.compile(r"RAMBA_[A-Z0-9_]*[A-Z0-9]")
_DOCS = ("docs/index.md", "README.md")


def _names_in(paths, suffixes=(".py",)):
    found = set()
    for top in paths:
        full = os.path.join(REPO, top)
        files = [full] if os.path.isfile(full) else [
            os.path.join(d, n) for d, _, names in os.walk(full)
            for n in names if n.endswith(suffixes)]
        for f in files:
            if os.path.abspath(f) == os.path.abspath(__file__):
                continue
            with open(f, encoding="utf-8") as fh:
                found.update(_NAME.findall(fh.read()))
    return found


_PACKAGE = sorted(_names_in(["ramba_tpu"]))
_DOCUMENTED = _names_in(_DOCS)


@pytest.mark.parametrize("name", _PACKAGE)
def test_knob_is_documented(name):
    assert name in _DOCUMENTED, f"{name} is read but named in neither {_DOCS}"


def test_docs_name_no_dead_knob():
    readers = _names_in(["ramba_tpu", "scripts", "benchmark", "tests",
                         "chip_smoke.py"], suffixes=(".py", ".sh"))
    dead = sorted(_DOCUMENTED - readers)
    assert not dead, f"documented but read nowhere: {dead}"
