#!/usr/bin/env python
"""Assert every ``LUMEN_*`` env knob referenced in ``lumen_tpu/`` is
documented in ``docs/`` (or README.md).

Undocumented knobs are how operators end up reading source to run a
server: every PR that adds a ``LUMEN_FOO`` env read must also land it in a
docs knob table. This check is collected by pytest
(``tests/test_check_knobs.py``) so tier-1 fails on the gap, and runs
standalone for a quick local scan::

    python scripts/check_knobs.py

Mechanics: a literal-regex scan (``LUMEN_[A-Z][A-Z0-9_]*``) over the
package source vs the same scan over the docs. Dynamically-composed names
(e.g. ``retry.py`` building ``LUMEN_{scope}_RETRIES``) don't match the
literal pattern in code — their concrete spellings are documented and the
composition sites carry the prefix only, which the scan ignores.
"""

from __future__ import annotations

import os
import re
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KNOB_RE = re.compile(r"LUMEN_[A-Z][A-Z0-9_]*")

#: Knobs that are deliberately undocumented in operator docs: test-harness
#: toggles (documented where they are used) and internal plumbing that is
#: not an operator surface. Keep this SHORT — the point of the check is
#: that the default for a new knob is "document it".
ALLOWLIST: set[str] = set()


def _scan(paths: list[str], exts: tuple[str, ...]) -> set[str]:
    found: set[str] = set()
    for root in paths:
        for dirpath, _, filenames in os.walk(root):
            if "__pycache__" in dirpath:
                continue
            for fn in filenames:
                if not fn.endswith(exts):
                    continue
                path = os.path.join(dirpath, fn)
                try:
                    with open(path, "r", encoding="utf-8", errors="ignore") as f:
                        found.update(KNOB_RE.findall(f.read()))
                except OSError:
                    continue
    return found


def referenced_knobs() -> set[str]:
    """Every literal LUMEN_* name in the package source."""
    return _scan([os.path.join(REPO_ROOT, "lumen_tpu")], (".py",))


def documented_knobs() -> set[str]:
    """Every literal LUMEN_* name in docs/ and README.md."""
    docs = _scan([os.path.join(REPO_ROOT, "docs")], (".md",))
    readme = os.path.join(REPO_ROOT, "README.md")
    if os.path.exists(readme):
        with open(readme, "r", encoding="utf-8", errors="ignore") as f:
            docs.update(KNOB_RE.findall(f.read()))
    return docs


def undocumented() -> list[str]:
    return sorted(referenced_knobs() - documented_knobs() - ALLOWLIST)


def main() -> int:
    missing = undocumented()
    if missing:
        print("undocumented LUMEN_* knobs (add to a docs/ knob table):")
        for name in missing:
            print(f"  {name}")
        return 1
    print(f"ok: {len(referenced_knobs())} referenced knobs all documented")
    return 0


if __name__ == "__main__":
    sys.exit(main())
