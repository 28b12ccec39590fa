#!/usr/bin/env python
"""Compare two compiled programs (HLO text) but for their metadata.

    python tools/strip_hlo.py A.hlo.txt[.gz] B.hlo.txt[.gz]

prints the unified diff of the two programs with every instruction's
``metadata={...}`` and the file, function and stack-frame tables removed,
and every ``%name`` renumbered by its order of first appearance (so that
programs that differ only in XLA's instruction numbering print the same),
and exits 1 when they differ.  With one file it prints that file so
stripped.  ``jax.named_scope`` changes only what this removes.
"""

from __future__ import annotations

import difflib
import gzip
import re
import sys

TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_METADATA_RE = re.compile(r",? metadata=\{[^}]*\}")
_NAME_RE = re.compile(r"%([\w.\-]+)")
_TABLE_ROW_RE = re.compile(r"^\d+ ")


def strip(hlo_text: str) -> str:
    """The program without metadata, its names renumbered."""
    names = {}

    def canon(m):
        base = re.sub(r"\.\d+", "", m.group(1))
        return "%" + names.setdefault(m.group(1), f"{base}#{len(names)}")

    out = []
    for line in hlo_text.splitlines():
        if line.startswith(TABLES) or _TABLE_ROW_RE.match(line):
            continue
        out.append(_NAME_RE.sub(canon, _METADATA_RE.sub("", line)))
    return "\n".join(out) + "\n"


def _read(path: str) -> str:
    with (gzip.open(path, "rt") if path.endswith(".gz") else open(path)) as f:
        return f.read()


def main(argv) -> int:
    if len(argv) == 1:
        sys.stdout.write(strip(_read(argv[0])))
        return 0
    if len(argv) != 2:
        print(__doc__.strip().splitlines()[2], file=sys.stderr)
        return 2
    a, b = (strip(_read(p)).splitlines(keepends=True) for p in argv)
    diff = list(difflib.unified_diff(a, b, argv[0], argv[1]))
    sys.stdout.writelines(diff)
    print(f"{len(a)} and {len(b)} lines; {'identical' if not diff else 'they differ'}")
    return 1 if diff else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
