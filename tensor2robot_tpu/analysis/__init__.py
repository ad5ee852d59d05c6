"""graftlint: static analysis for configs, specs, and tracer hygiene.

The framework's core promise is spec-driven correctness — configs,
TensorSpecs and the pipeline must agree (SURVEY.md §0). Before this
subsystem those contracts were enforced only at runtime (fresh-process
config smoke test, call-time spec validation) and by convention (the
CLAUDE.md rules). `graftlint` checks them *before any JAX backend is
touched*, so linting never takes the chip from the job that owns it —
the compiler-first discipline of arxiv
1810.09868 / 2204.06514 applied to framework plumbing.

Three analyzers, one CLI (`python -m tensor2robot_tpu.analysis.lint`):

* `config_check`  — per-binding static resolution of every `.gin` file
  against the configurable registry (no-execute parse via
  `utils.config.iter_config_statements`);
* `tracer_check`  — AST lint for backend-initialization, tracing and
  device-timing hazards (import-time backend touches, host syncs and
  impure calls inside jitted functions, un-barriered timing windows);
* `spec_check`    — TensorSpec sharding axes vs mesh axis names declared
  in configs, plus structure-level feature/label conflict checks.

Analysis NEVER initializes a JAX backend (pinned by
tests/test_static_analysis.py, which runs the CLI under a bogus
JAX_PLATFORMS trap). Findings are structured (file, line, rule, message);
`# graftlint: disable=<rule>` on the offending line suppresses.
"""

from tensor2robot_tpu.analysis.findings import Finding  # noqa: F401
