#!/usr/bin/env python
"""Thin compatibility shim over scripts/raylint (rule: kernel-fallbacks).

The logic lives in scripts/raylint/rules_legacy.py; this entry point
keeps the historical CLI (`python scripts/check_kernel_fallbacks.py`)
for existing tier-1 wiring. Repo-wide enforcement runs through
`python -m scripts.raylint` (tests/test_raylint.py).
"""

from __future__ import annotations

import sys
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent
if str(_REPO) not in sys.path:
    sys.path.insert(0, str(_REPO))

from scripts.raylint import Project, run  # noqa: E402
from scripts.raylint.rules_legacy import (  # noqa: E402,F401 - compat API
    REQUIRED_FLAGS,
    cfg_reads,
    defined_flags,
)


def main() -> int:
    project = Project(_REPO)
    result = run(project, rules=["kernel-fallbacks"])
    for f in result.findings:
        print(f"{f.location}: {f.message}")
    if result.findings:
        return 1
    config = project.file("ray_tpu/core/config.py")
    flags = defined_flags(config.tree) if config else set()
    print(
        f"check_kernel_fallbacks: ok ({len(flags)} registered flags, "
        f"all cfg reads resolve, Pallas kernels keep a reference oracle "
        f"and no run-time fallback to it)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
