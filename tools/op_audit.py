"""Op-registry audit gate (r4 review #7).

Mechanically extracts the reference's operator inventory (every
REGISTER_OPERATOR / REGISTER_OP_WITHOUT_GRADIENT / REGISTER_ELEMWISE_* /
REGISTER_OP_CPU_KERNEL registration plus the FOR_EACH_ACTIVATION_OP macro
list) and requires every non-grad name to be either

  * registered in paddle_tpu.core.registry, or
  * recorded in OP_DEVIATIONS.md with a category + rationale
    (categories: alias — differently factored, with the covering name;
     design — subsumed by the XLA/JAX architecture; nonpublic — no API.spec
     surface in the reference itself; infra — device/runtime plumbing with
     an architectural replacement).

Stale deviation rows (name now registered, or gone from the reference) fail
the gate too, so the file cannot rot.  Reference precedent for freezing
internals: op_use_default_grad_op_maker.spec.

  python tools/op_audit.py            # human summary, exit 1 on failure
  python tools/op_audit.py --json     # machine-readable
"""
from __future__ import annotations

import json
import os
import re
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_OPS_DIR = "/root/reference/paddle/fluid/operators"
DEVIATIONS = os.path.join(REPO, "OP_DEVIATIONS.md")
SNAPSHOT = os.path.join(REPO, "tools", "ref_op_inventory.txt")

_PATTERNS = [
    re.compile(r"REGISTER_OPERATOR\(\s*([a-z0-9_]+)"),
    re.compile(r"REGISTER_OP_WITHOUT_GRADIENT\(\s*([a-z0-9_]+)"),
    re.compile(r"REGISTER_ELEMWISE_[A-Z_]*OP[A-Z_]*\(\s*([a-z0-9_]+)"),
    re.compile(r"REGISTER_OP_CPU_KERNEL\(\s*([a-z0-9_]+)"),
]
_ACT_MACRO = re.compile(r"__macro\(\s*([a-z0-9_]+)\s*,")


def reference_inventory():
    """Scan the reference tree; fall back to the committed snapshot when the
    reference checkout is absent (CI on a bare clone)."""
    names = set()
    if os.path.isdir(REF_OPS_DIR):
        for root, _dirs, files in os.walk(REF_OPS_DIR):
            for f in files:
                if not (f.endswith(".cc") or f.endswith(".h") or f.endswith(".cu.cc")):
                    continue
                try:
                    text = open(os.path.join(root, f), errors="ignore").read()
                except OSError:
                    continue
                for pat in _PATTERNS:
                    names.update(pat.findall(text))
                if f == "activation_op.h":
                    names.update(_ACT_MACRO.findall(text))
        names = {n for n in names
                 if not n.endswith("_grad") and not n.endswith("_grad2")}
        # macro-template placeholders, not ops (e.g. isfinite_op.cc's
        # `REGISTER_OPERATOR(op_type, ...)` inside a #define)
        names -= {"op_type", "op_name"}
        with open(SNAPSHOT, "w") as fh:
            fh.write("\n".join(sorted(names)) + "\n")
        return names
    if os.path.exists(SNAPSHOT):
        return set(open(SNAPSHOT).read().split())
    raise SystemExit("neither the reference tree nor the snapshot exists")


def load_deviations():
    """Parse OP_DEVIATIONS.md table rows: | op | category | rationale |."""
    devs = {}
    if not os.path.exists(DEVIATIONS):
        return devs
    for line in open(DEVIATIONS):
        m = re.match(r"\|\s*`?([a-z0-9_]+)`?\s*\|\s*(\w+)\s*\|\s*(.+?)\s*\|\s*$",
                     line)
        if m and m.group(2) in ("alias", "design", "nonpublic", "infra"):
            devs[m.group(1)] = (m.group(2), m.group(3))
    return devs


def audit():
    import paddle_tpu  # noqa: F401  (populates the registry)
    from paddle_tpu.core import registry

    ref = reference_inventory()
    ours = set(registry._REGISTRY)
    devs = load_deviations()

    registered = sorted(ref & ours)
    recorded = sorted(n for n in ref - ours if n in devs)
    uncovered = sorted(n for n in ref - ours if n not in devs)
    stale = sorted(n for n in devs if n in ours or n not in ref)
    return {
        "ref_total": len(ref),
        "registered": len(registered),
        "recorded": len(recorded),
        "uncovered": uncovered,
        "stale_deviations": stale,
        "ok": not uncovered and not stale,
    }


def main():
    res = audit()
    if "--json" in sys.argv:
        print(json.dumps(res, indent=1))
    else:
        print(f"reference non-grad ops: {res['ref_total']}")
        print(f"registered:             {res['registered']}")
        print(f"recorded deviations:    {res['recorded']}")
        if res["uncovered"]:
            print(f"UNCOVERED ({len(res['uncovered'])}): {' '.join(res['uncovered'])}")
        if res["stale_deviations"]:
            print(f"STALE deviation rows: {' '.join(res['stale_deviations'])}")
        print("GATE:", "PASS" if res["ok"] else "FAIL")
    sys.exit(0 if res["ok"] else 1)


if __name__ == "__main__":
    main()
