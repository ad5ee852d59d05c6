"""Compile cache: seconds from the call of `train_eval_model` to the end of
step 1 (the hook's clock, closed by a barrier on the state)."""


def read(run):
  return run.get("first_step_s")
