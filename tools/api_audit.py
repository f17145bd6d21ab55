"""Audit the public API against the reference's API.spec (r3 review #6).

For every entry in /root/reference/paddle/fluid/API.spec (936 lines), the
name `paddle.fluid.X.y` must either RESOLVE on `paddle_tpu` (getattr chain —
this counts inherited methods the spec-dump tool doesn't enumerate) or be
RECORDED with a one-line rationale in API_DEVIATIONS.md.

Run:  python tools/api_audit.py           # print unresolved, unrecorded
      python tools/api_audit.py --counts  # summary numbers
The gate test (tests/test_api_audit.py) asserts the unrecorded set is empty.
"""
from __future__ import annotations

import os
import re
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_SPEC = "/root/reference/paddle/fluid/API.spec"
DEVIATIONS = os.path.join(REPO, "API_DEVIATIONS.md")


def reference_entries():
    names = []
    with open(REF_SPEC) as f:
        for line in f:
            name = line.split(" ")[0].strip()
            if name.startswith("paddle.fluid."):
                names.append(name[len("paddle.fluid."):])
            elif name == "paddle.fluid":
                continue
    return sorted(set(names))


def resolves(name: str) -> bool:
    import paddle_tpu

    obj = paddle_tpu
    for part in name.split("."):
        try:
            obj = getattr(obj, part)
        except AttributeError:
            return False
    return True


def recorded_deviations():
    """Entries claimed in API_DEVIATIONS.md: `code`-quoted names in the
    subject part of a bullet (before the em-dash rationale); prose mentions
    inside rationales don't count."""
    if not os.path.exists(DEVIATIONS):
        return set()
    out = set()
    in_subject = False
    for line in open(DEVIATIONS):
        if line.startswith("- "):
            in_subject = True
        elif not line.startswith("  "):
            in_subject = False
        if not in_subject:
            continue
        had_dash = "\u2014" in line
        subject = line.split("\u2014")[0]
        for m in re.finditer(r"`([A-Za-z_][\w.]*)`", subject):
            out.add(m.group(1))
        if had_dash:
            in_subject = False
    return out


def audit():
    entries = reference_entries()
    recorded = recorded_deviations()
    resolved, recorded_hits, unrecorded = [], [], []
    for name in entries:
        if resolves(name):
            resolved.append(name)
        elif name in recorded or any(
            name == r or name.startswith(r + ".") for r in recorded
        ):
            recorded_hits.append(name)
        else:
            unrecorded.append(name)
    return resolved, recorded_hits, unrecorded


def main():
    resolved, recorded, unrecorded = audit()
    total = len(resolved) + len(recorded) + len(unrecorded)
    if "--counts" in sys.argv:
        print(f"reference entries: {total}")
        print(f"resolved on paddle_tpu: {len(resolved)}")
        print(f"recorded in API_DEVIATIONS.md: {len(recorded)}")
        print(f"UNRECORDED (gate fails): {len(unrecorded)}")
        return
    for name in unrecorded:
        print(name)


if __name__ == "__main__":
    main()
