"""Step program: device ms a step under ops that XLA names after the step's
`optimizer` and `ema` scopes: the update of the parameters, the optimizer's
state and the moving average, less what XLA fused into a weight-gradient
fusion and named after the gradient (`step_backward_ms` holds that). So a
lower bound: the upper one is `held by` in the `[bench scopes]` table
(`shared_by_phase`). It is worth listing only where the update has fusions of
its own, the expert cells; on g44 and seq all of it is fused away and this
reads under 0.1 ms against 28.6 and 13.4 ms held (my chip runs, PR 37)."""

from benchmarks.layer_metrics import device_scopes


def read(run):
  return device_scopes.phase_ms(run, "optimizer", "ema")
