"""Which case ids a PR lost and gained: ``python tests/idmap.py parent.xml
change.xml`` over the ``--junitxml`` files of two whole runs (PR 50's record
of 164 moved ids in ``CHANGES.md`` is this script's output)."""

import collections
import re
import sys
import xml.etree.ElementTree as ET


def ids(xml):
    out = {}
    for case in ET.parse(xml).getroot().iter("testcase"):
        marks = [c.tag for c in case if c.tag in ("failure", "error", "skipped")]
        out[f"{case.get('classname').replace('.', '/')}.py::"
            f"{case.get('name')}"] = marks[0] if marks else "passed"
    return out


if __name__ == "__main__":
    parent, change = ids(sys.argv[1]), ids(sys.argv[2])
    for said, names in (("gone", set(parent) - set(change)),
                        ("new", set(change) - set(parent))):
        by = collections.Counter(re.sub(r"\[.*", "", n) for n in names)
        print(f"{len(names)} ids {said}, by function:")
        for name, n in sorted(by.items()):
            print(f"  {n:3d} {name}")
    for said, run in (("parent", parent), ("change", change)):
        print(said, dict(collections.Counter(run.values())),
              *(n for n, v in run.items() if v in ("failure", "error")))
