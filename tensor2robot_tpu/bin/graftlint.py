"""Static-analysis CLI: graftlint over configs, specs, and sources.

Thin bin/ face of `tensor2robot_tpu.analysis.lint` (repo convention:
user-facing entry points live under bin/). Unlike its siblings this CLI
is argparse-based — no absl flags — because it must stay importable next
to them and must never drag in anything that could touch a JAX backend
beyond plain imports.

Usage:
  python -m tensor2robot_tpu.bin.graftlint tensor2robot_tpu scripts
  python -m tensor2robot_tpu.bin.graftlint --list-rules

Exits non-zero iff findings remain after `# graftlint: disable=`
suppressions. See docs/ARCHITECTURE.md "The analysis layer" for the rule
catalog; `scripts/lint.sh` wraps this with a CPU pin, so linting never
takes the chip.
"""

from __future__ import annotations

import sys

from tensor2robot_tpu.analysis import lint


def main(argv=None) -> int:
  return lint.main(argv)


if __name__ == "__main__":
  sys.exit(main())
